"""Every backend compile request JAX makes while the benchmark runs.

Copied from chip_smoke.py's CompileLog: (jitted function name,
seconds, served by the persistent cache), from JAX's own monitoring
events.  A compile inside the measured window makes the run incorrect.
"""
from __future__ import annotations

import threading


class CompileLog:
    def __init__(self):
        from jax import monitoring
        self.events: list[tuple[str, float, bool]] = []
        self._hit = threading.local()
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.flag = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), secs,
                                getattr(self._hit, "flag", False)))
            self._hit.flag = False

    def __len__(self) -> int:
        return len(self.events)
