"""Build/load the C++ fast-path module (cometbft_tpu._native).

The native source lives in native/ at the repo root; it is compiled
on first use with g++ (no external deps — SHA-256 is self-contained)
and cached next to this package.  Pure-Python implementations remain
the fallback everywhere, gated by COMETBFT_TPU_NATIVE=0.
"""
# bftlint: disable-file=blocking-in-async
# Justified: every blocking call here (cpuinfo probe, freshness tag
# read, g++ subprocess) runs at most once per process — load() is
# memoized via _mod/_failed, hot paths call load(allow_build=False)
# which never compiles, and the node pre-builds in a worker thread at
# startup.  Without this, the interprocedural may_block summary would
# taint every async caller of batched_hashes with an unreachable
# build chain.
from __future__ import annotations

import os
import subprocess
import sysconfig
import threading
import time
from typing import Optional

_mod = None
_failed = False
# load(allow_build=False) found nothing fresh on disk: the hot paths
# (wire.encode runs once a message) take that answer until this
# monotonic time and do not stat nine files a call meanwhile
_miss_until = 0.0
_build_lock = threading.Lock()


def _source_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "native")


def _target_path() -> str:
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "_native" + suffix)


def _sources() -> list[str]:
    d = _source_dir()
    return [os.path.join(d, "_native.cpp"),
            os.path.join(d, "sha256.hpp"),
            os.path.join(d, "sha256_ni.hpp"),
            os.path.join(d, "sha512.hpp"),
            os.path.join(d, "sha512_mb.hpp"),
            os.path.join(d, "bls12381.hpp"),
            os.path.join(d, "ed25519_msm.hpp"),
            os.path.join(d, "chacha20poly1305.hpp"),
            os.path.join(d, "wire_codec.hpp"),
            os.path.join(d, "commit_walk.hpp")]


def _host_tag() -> str:
    """Fingerprint of this machine's CPU features.  The module is
    built with -march=native, so a cached .so copied to a different
    CPU (container image, rsync'd tree) must be treated as STALE and
    rebuilt — importing it could SIGILL, which no except clause can
    catch."""
    import hashlib
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha256(
                        line.encode()).hexdigest()[:16]
    except OSError:
        pass
    import platform
    return hashlib.sha256(
        platform.processor().encode()).hexdigest()[:16]


def _target_fresh() -> bool:
    """True when the built module exists, is newer than EVERY native
    source file (missing sources count as stale, not error), and was
    built on a machine with this CPU's feature set."""
    try:
        t = os.path.getmtime(_target_path())
        if not all(t >= os.path.getmtime(s) for s in _sources()):
            return False
        with open(_target_path() + ".host") as f:
            return f.read().strip() == _host_tag()
    except OSError:
        return False


def _build() -> Optional[str]:
    """Compile to a temp file and atomically rename into place, under
    a lock — a concurrent load(allow_build=False) must never see a
    half-written .so."""
    src = _sources()[0]
    if not os.path.exists(src):
        return None
    target = _target_path()
    with _build_lock:
        if _target_fresh():
            return target
        include = sysconfig.get_paths()["include"]
        tmp = target + f".build-{os.getpid()}"
        base = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                f"-I{include}", f"-I{_source_dir()}", src, "-o", tmp]
        # -march=native is safe here (the module is always built on
        # the machine that runs it) and buys ~15% on the Montgomery
        # bigint paths; retry portable if the flag is rejected
        for cmd in (base[:1] + ["-march=native"] + base[1:], base):
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               timeout=120)
                os.replace(tmp, target)
                with open(target + ".host", "w") as f:
                    f.write(_host_tag())
                return target
            except (OSError, subprocess.SubprocessError):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        return None


def load(allow_build: bool = True):
    """The _native module, or None (no compiler / disabled).

    With allow_build=False this never shells out to g++ — it only
    imports an already-built module.  Hot paths (merkle hashing runs
    inside the consensus loop) use that form; the node pre-builds in
    a thread at startup, and CLIs/tests build on first use."""
    global _mod, _failed, _miss_until
    if _mod is not None:
        return _mod
    if _failed or os.environ.get("COMETBFT_TPU_NATIVE", "1") == "0":
        return None
    if not allow_build and time.monotonic() < _miss_until:
        return None
    if not _target_fresh():
        if not allow_build:
            _miss_until = time.monotonic() + 1.0
            return None
        if _build() is None:
            _failed = True
            return None
    try:
        from cometbft_tpu import _native  # noqa: F401
        _mod = _native
    except ImportError:
        _failed = True
        _mod = None
    return _mod


def batched_hashes(fn_name: str, items,
                   min_items: int = 8) -> Optional[list]:
    """Run one of the module's batch digest functions (sha256_many /
    leaf_hashes) and split the concatenated 32-byte output — or None
    when the batch is small, the module isn't built yet (never builds
    here: hot paths), or the items aren't plain bytes."""
    if len(items) < min_items:
        return None
    mod = load(allow_build=False)
    if mod is None:
        return None
    try:
        cat = getattr(mod, fn_name)(list(items))
    except TypeError:
        return None
    return [cat[i * 32:(i + 1) * 32] for i in range(len(items))]


def prebuild_async() -> None:
    """Kick the g++ build on a daemon thread (node startup calls this
    so the first big merkle hash never blocks the event loop)."""
    import threading
    threading.Thread(target=load, daemon=True,
                     name="native-build").start()
