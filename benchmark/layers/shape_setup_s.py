"""Seconds set-up spent setting kernel shapes up: kernel_compile spans
(the program's warm-up) plus dispatches that found their shape cold
(trace + lowering + compile or cache load + one run, inside a call)."""


def read(obs):
    return sum(ev["dur_ns"] for ev in obs.setup_spans) / 1e9
