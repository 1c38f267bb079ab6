"""Batch-verifier dispatch: key type + configured backend -> BatchVerifier.

Reference: crypto/batch/batch.go — CreateBatchVerifier (:10),
SupportsBatchVerifier (:21); only ed25519 supports batching.

TPU-native addition: a process-global backend selector, set with
``set_backend()`` or COMETBFT_TPU_CRYPTO_BACKEND.  Backends:
  * "tpu"  — the data-parallel device verifier (ops/ed25519_jax.py).
             Asking for it on a host whose JAX platform is not a TPU
             is an error at selection time (ops/device.py decides).
  * "cpu"  — the native RLC batch equation over a Pippenger MSM
             (crypto/ed25519.CpuBatchVerifier); never imports JAX.
  * "auto" — "tpu" when ops/device.py finds a TPU, else "cpu";
             resolved once per process, blocking, and logged.

The device verifier does not wait for ``verify()``: the moment
``add()`` has filled one pipeline tile (1,024 lanes) it hands that
tile to the device pipeline, whose host prep runs on a native thread
beside the adding one; the tile is launched by the next hand-off and
its kernel runs while the caller goes on adding; ``verify()`` hands
over the remainder and settles (GuardedTpuBatchVerifier).  A batch
below one tile is one dispatch from ``verify()``, as ever.

Every verifier this module hands out also answers ``verify_async()``
(keys.BatchVerifier): an awaitable verdict future whose work runs on
the shared verification staging worker (crypto/pipeline.py) — the
Traced/Guarded wrappers keep their synchronous semantics because the
wrapped ``verify()`` is exactly what executes off-loop (tiles that
``add()`` launched are settled there), and large ed25519 CPU batches
additionally pipeline pad-bucket tiles inside it (overlapped
host_prep / GIL-free kernel, per-tile reject bisection).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Optional

from ..libs import tracing
from . import ed25519
from .keys import BatchVerifier, PubKey
from .pipeline import bucket as pad_bucket, tile_bucket

# ---------------------------------------------------------------------
# metrics v2: batch-verify latency distribution, labeled by backend and
# pad bucket.  Registered lazily on the process-global registry
# (libs.metrics.DEFAULT) because verifiers are created deep in the
# verification paths with no node context; the node's /metrics merges
# DEFAULT in.  The label value is ``pad_bucket(n)``, which is
# crypto/pipeline.bucket — the one ladder the device dispatch pads to —
# so CPU and TPU observations of the same batch size share a label
# value, refined buckets included.

_VERIFY_HIST = None


def verify_seconds_histogram():
    """The process-global batch-verify latency histogram."""
    global _VERIFY_HIST
    if _VERIFY_HIST is None:
        from ..libs import metrics as libmetrics
        _VERIFY_HIST = libmetrics.DEFAULT.histogram(
            "crypto", "batch_verify_seconds",
            "Batch signature verification latency in seconds, by "
            "dispatch backend and kernel pad bucket.",
            labels=("backend", "pad_bucket"),
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
    return _VERIFY_HIST


def _observe_verify(backend: str, n: int, elapsed_s: float) -> None:
    verify_seconds_histogram().with_labels(
        backend, str(pad_bucket(n))).observe(elapsed_s)

_backend: Optional[str] = None      # set_backend() choice; None = env/auto
_resolved: Optional[str] = None     # what env/auto resolved to, once
_resolve_lock = threading.Lock()


def set_backend(name: str) -> None:
    """Select the batch-verification backend: 'tpu', 'cpu', or 'auto'.
    'tpu' without a TPU raises here, not at the first batch."""
    global _backend
    if name not in ("tpu", "cpu", "auto"):
        raise ValueError(f"unknown crypto backend {name!r}")
    if name == "tpu":
        from ..ops import device
        device.require_tpu()
    _backend = None if name == "auto" else name


def get_backend() -> str:
    """The backend in force.  Without set_backend() the choice comes
    from COMETBFT_TPU_CRYPTO_BACKEND (default auto) and is resolved
    ONCE: the first ask blocks on JAX's backend start-up — seconds on a
    chip host, so the node asks at start, off the event loop — and the
    answer never flips mid-run."""
    global _resolved
    if _backend is not None:
        return _backend
    if _resolved is None:
        with _resolve_lock:
            if _resolved is None:
                _resolved = _resolve_backend()
    return _resolved


def _resolve_backend() -> str:
    env = os.environ.get("COMETBFT_TPU_CRYPTO_BACKEND", "").lower() \
        or "auto"
    if env == "cpu":
        return "cpu"
    if env not in ("tpu", "auto"):
        raise ValueError(
            f"COMETBFT_TPU_CRYPTO_BACKEND={env!r}: expected tpu|cpu|auto")
    from ..libs.log import new_logger
    from ..ops import device
    log = new_logger("crypto")
    if env == "tpu":
        dev = device.require_tpu()
    else:
        try:
            dev = device.probe()
        except ImportError as e:
            # no JAX installed is a CPU-only host, not a lost device;
            # a backend that fails to START raises out of here instead
            log.info("crypto backend resolved", backend="cpu",
                     reason=f"jax not importable: {e}")
            return "cpu"
    backend = "tpu" if dev.is_tpu else "cpu"
    log.info("crypto backend resolved", backend=backend,
             requested=env, platform=dev.platform,
             device_kind=dev.kind, devices=dev.count,
             compile_cache=dev.cache_dir)
    return backend


# bls12381.KEY_TYPE, spelled locally so this module does not import
# the (native-backed) bls12381 stack at process start; asserted equal
# in tests/test_batch_grouped.py
_BLS_KEY_TYPE = "bls12_381"


def supports_batch_verifier(pub_key: PubKey) -> bool:
    """ed25519 (reference: batch.go:21) and — beyond the reference,
    which drives blst strictly per-signature — bls12381 via the
    random-linear-combination pairings-product verifier."""
    return pub_key.type() in (ed25519.KEY_TYPE, _BLS_KEY_TYPE)


def batch_verify_by_type(entries) -> list:
    """Best-effort batch verification of (pub_key, msg, sig) triples
    grouped by key type.  Returns a per-entry list: True/False for
    entries a batch verifier judged, None for entries it could not
    (unsupported key type, malformed input, singleton group, verifier
    error) — callers treat None as "verify it yourself".  Never
    raises.  (types/validation.py's grouped commit path keeps its own
    walk because it must interleave caching and lowest-failing-index
    error semantics; this helper serves advisory callers like the
    vote-burst pre-verification.)"""
    out = [None] * len(entries)
    groups: dict[str, tuple] = {}
    for i, (pub_key, msg, sig) in enumerate(entries):
        try:
            if not supports_batch_verifier(pub_key):
                continue
            kt = pub_key.type()
            entry = groups.get(kt)
            if entry is None:
                entry = (create_batch_verifier(pub_key), [])
                groups[kt] = entry
            entry[0].add(pub_key, msg, sig)
            entry[1].append(i)
        except Exception:
            continue
    for bv, idxs in groups.values():
        if len(idxs) < 2:
            continue
        try:
            _, mask = bv.verify()
        except Exception:
            continue
        for i, good in zip(idxs, mask):
            out[i] = bool(good)
    return out


# --- TPU dispatch circuit breaker ------------------------------------
# A validator that loses its device mid-run must keep voting, so a
# failed kernel compile/dispatch falls back to the CPU verifier for
# that batch.  A compile failure is deterministic per process:
# without a breaker the dispatch re-attempted — and re-paid — it on
# EVERY batch.  The first non-transient failure latches the breaker
# open and every later batch goes straight to the CPU verifier;
# transient faults open it for a timeout and then re-probe once.  The
# fallback is loud: the caught exception is logged when it opens the
# breaker, the state is exported on the process-global metrics
# registry, and measurement commands (chip_smoke.py, bench.py) treat
# a breaker that is not closed as failure.

_TPU_BREAKER = None


def tpu_breaker():
    """The process-global breaker guarding TPU kernel dispatch."""
    global _TPU_BREAKER
    if _TPU_BREAKER is None:
        from ..libs import metrics as libmetrics
        from ..libs.breaker import CircuitBreaker
        from ..libs.breaker import Metrics as BreakerMetrics
        _TPU_BREAKER = CircuitBreaker(
            "crypto_tpu_kernel", failure_threshold=1,
            reset_timeout_s=float(os.environ.get(
                "COMETBFT_TPU_BREAKER_RESET_S", "300")),
            metrics=BreakerMetrics(libmetrics.DEFAULT))
    return _TPU_BREAKER


def reset_tpu_breaker() -> None:
    """Test hook: discard the process-global breaker."""
    global _TPU_BREAKER
    _TPU_BREAKER = None


_TRANSIENT_MARKERS = ("timeout", "timed out", "deadline", "unavailable",
                      "resource_exhausted", "connection", "aborted")


def _is_transient_kernel_error(e: BaseException) -> bool:
    """Conservative classification: connection/timeout shapes re-probe
    after a cooldown; anything else (compile/lowering/platform errors)
    is deterministic for this process and latches the breaker."""
    if isinstance(e, (TimeoutError, ConnectionError)):
        return True
    s = f"{type(e).__name__}: {e}".lower()
    return any(m in s for m in _TRANSIENT_MARKERS)


_NEVER = float("inf")


class GuardedTpuBatchVerifier(BatchVerifier):
    """TPU batch verifier behind the process-global circuit breaker,
    which starts before its caller has finished adding.

    add() appends the raw triple the native prep reads and compares a
    length; when the items not yet handed over fill one pipeline tile
    (crypto/pipeline.tile_bucket, 1,024 lanes) and the breaker is
    closed, exactly that many go to the device pipeline then and
    there (ops/ed25519_jax.TilePipeline.feed): their host prep
    begins on the native module's prep thread and the tile handed
    over before them, whose prep is done by now, is launched, so the
    kernels run while the caller is still walking its commit and a
    streamed tile costs the adding thread its hand-off and a launch.
    verify() feeds the remainder at the same shape and settles: of a
    10,000-validator commit's seven tiles, five are launched inside
    the walk.  A batch that never fills a tile — every validator set
    below 1,024 signatures a batch — is untouched: verify() makes the
    one dispatch it always made.  What the verifier does depends on
    the number of items added and on nothing else.

    A verifier dropped with a prep or a tile in flight (the walk
    raised, the tally fell short) leaves nothing behind: the prep's
    handle takes its job back or lets it finish, the device finishes
    kernels nobody reads, no span of them records, the breaker hears
    nothing.  The breaker is asked for a probe only by verify(),
    which always reports back; add() feeds under a closed breaker
    alone.

    verify() attempts the device kernel only while the breaker admits
    it; a dispatch failure, in add() or in verify(), records against
    the breaker (latched open for non-transient faults, so the failing
    kernel is attempted at most once per process), is logged with its
    type and message, and the SAME batch, whole, falls back to the CPU
    verifier, which takes the same raw triples — callers
    always get a verdict.

    The batch_verify span opens when the verifier first does work on
    the device path — the first tile fed from add(), else verify() —
    and ends with the verdict; crypto_batch_verify_seconds observes
    the same two clock readings.  A span opened from add() has the
    span that was open when the verifier was made as its parent
    (commit_verify), not the walk it overlaps."""

    def __init__(self, breaker=None):
        self._breaker = breaker if breaker is not None else tpu_breaker()
        # (pub, msg, sig) as bytes: what the native prep reads
        self._items: list[tuple[bytes, bytes, bytes]] = []
        self._tile = tile_bucket()
        self._feed_at = self._tile  # len(_items) at which add() feeds
        self._fed = 0               # items handed to the pipeline
        self._pipe = None           # ops TilePipeline, once a tile is fed
        self._span = None           # batch_verify, once begun
        self._eager_failed = False
        self._creator = tracing.current()

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type() != ed25519.KEY_TYPE:
            raise TypeError("GuardedTpuBatchVerifier requires ed25519 keys")
        if len(sig) != 64:
            raise ValueError("malformed signature")
        items = self._items
        items.append((pub_key.bytes(), bytes(msg), bytes(sig)))
        if len(items) >= self._feed_at:
            self._feed_eagerly()

    def extend_raw(self, triples) -> None:
        """add() for raw (pub, msg, sig) byte triples that already hold
        what add() checks: 32-byte ed25519 keys, 64-byte signatures.
        The native commit walk hands its chunks here
        (types/validation._walk_native), a tile at a time; a tile is
        fed at the same item counts as from add()."""
        items = self._items
        items.extend(triples)
        while len(items) >= self._feed_at:
            self._feed_eagerly()

    def __len__(self) -> int:
        return len(self._items)

    def _feed_eagerly(self) -> None:
        """One tile's worth of items is waiting: dispatch it, unless
        the breaker is anything but closed (then verify() decides,
        with the whole batch).  A failure is verify()'s failure, met
        early: recorded here, and the whole batch goes to the CPU
        verifier."""
        from ..libs.breaker import CLOSED
        if self._breaker.state != CLOSED:
            self._feed_at = _NEVER
            return
        try:
            self._feed(eager=True)
        except Exception as e:  # noqa: BLE001 — verify() falls back
            self._eager_failed = True
            self._device_failed(e)
        else:
            self._feed_at += self._tile

    def _feed(self, eager: bool = False) -> None:
        """Hand the next (at most) tile of items to the pipeline."""
        if self._pipe is None:
            with tracing.under(self._creator):
                self._begin_span()
            from ..ops.ed25519_jax import TilePipeline
            self._pipe = TilePipeline(self._tile)
        lo, self._fed = self._fed, min(self._fed + self._tile,
                                       len(self._items))
        with tracing.under(self._span):
            self._pipe.feed(self._items[lo:self._fed], eager=eager)

    def _begin_span(self) -> None:
        self._span = tracing.timed(tracing.CRYPTO, "batch_verify",
                                   backend="tpu").begin()

    def _end_span(self, **attrs) -> None:
        self._span.note(batch=len(self._items), **attrs)
        self._span.end()

    def _drop_pipeline(self) -> None:
        """Forget what add() dispatched; whatever runs next takes the
        whole batch and add() feeds no more."""
        self._pipe = self._span = None
        self._fed = 0
        self._feed_at = _NEVER

    def _device_failed(self, e: BaseException) -> None:
        br = self._breaker
        latch = not _is_transient_kernel_error(e)
        br.record_failure(latch=latch)
        from ..libs.log import new_logger
        new_logger("crypto").error(
            "TPU batch verify failed; falling back to the CPU "
            "verifier", error=f"{type(e).__name__}: {e}",
            batch=len(self._items), breaker=br.state,
            latched=latch, exc_info=True)
        self._end_span(error=type(e).__name__)
        self._drop_pipeline()

    def _verify_on_device(self):
        if self._pipe is not None:
            while self._fed < len(self._items):
                self._feed()
            with tracing.under(self._span):
                return self._pipe.finish()
        self._begin_span()
        from ..ops.ed25519_jax import verify_batch
        with tracing.under(self._span):
            return verify_batch(self._items)

    def verify(self):
        br = self._breaker
        attempted_tpu = self._eager_failed
        if not attempted_tpu and br.allow():
            attempted_tpu = True
            try:
                out = self._verify_on_device()
            except Exception as e:  # noqa: BLE001 — fall back below
                self._device_failed(e)
            else:
                br.record_success()
                self._end_span()
                _observe_verify("tpu", len(self._items),
                                self._span.seconds)
                self._drop_pipeline()
                return out
        self._drop_pipeline()
        t0 = time.perf_counter()
        with tracing.span(tracing.CRYPTO, "batch_verify",
                          batch=len(self._items), backend="cpu",
                          fallback=attempted_tpu):
            cpu = ed25519.CpuBatchVerifier()
            cpu.extend_raw(self._items)
            out = cpu.verify()
        _observe_verify("cpu", len(self._items),
                        time.perf_counter() - t0)
        return out


class TracedBatchVerifier(BatchVerifier):
    """Flight-recorder span around any BatchVerifier's dispatch —
    every batch shows up in /trace with its size and backend label."""

    def __init__(self, inner: BatchVerifier, backend: str):
        self._inner = inner
        self._backend = backend

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        self._inner.add(pub_key, msg, sig)

    def extend_raw(self, triples) -> None:
        self._inner.extend_raw(triples)

    def __len__(self) -> int:
        try:
            return len(self._inner)
        except TypeError:   # verifier without __len__ (bls)
            return len(getattr(self._inner, "_items", ()))

    def verify(self):
        n = len(self)
        t0 = time.perf_counter()
        with tracing.span(tracing.CRYPTO, "batch_verify",
                          batch=n, backend=self._backend):
            out = self._inner.verify()
        _observe_verify(self._backend, n, time.perf_counter() - t0)
        return out


def create_batch_verifier(pub_key: PubKey) -> BatchVerifier:
    """Reference: batch.go:10 — errors for unsupported key types."""
    if pub_key.type() == _BLS_KEY_TYPE:
        from . import bls12381
        return TracedBatchVerifier(bls12381.Bls12381BatchVerifier(),
                                   "bls_native")
    if pub_key.type() != ed25519.KEY_TYPE:
        raise ValueError(f"batch verification unsupported for {pub_key.type()}")
    if get_backend() == "tpu":
        return GuardedTpuBatchVerifier()   # traces internally
    return TracedBatchVerifier(ed25519.CpuBatchVerifier(), "cpu")
