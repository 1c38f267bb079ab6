"""Failure-domain supervision: supervisor restart policies, circuit
breaker states, TPU-dispatch breaker latching, ABCI deadlines, and
the extended FuzzedConnection write faults.
"""
import asyncio

import pytest

from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.breaker import (
    CLOSED, HALF_OPEN, LATCHED_OPEN, OPEN, CircuitBreaker,
)
from cometbft_tpu.libs.breaker import Metrics as BreakerMetrics
from cometbft_tpu.libs.supervisor import (
    Metrics as SupMetrics,
    RestartPolicy,
    Supervisor,
)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ---------------------------------------------------------------------
# Supervisor

class TestSupervisor:
    def test_crash_restarts_loop_with_metrics(self):
        async def go():
            reg = libmetrics.Registry()
            sup = Supervisor("t", metrics=SupMetrics(reg))
            runs = []

            async def loop():
                runs.append(1)
                if len(runs) < 3:
                    raise RuntimeError("boom")
                # third incarnation parks until cancelled
                await asyncio.Event().wait()

            st = sup.spawn(loop, name="loop", kind="loop",
                           policy=RestartPolicy(max_restarts=5,
                                                backoff_base_s=0.001,
                                                backoff_max_s=0.01,
                                                jitter=0.0))
            for _ in range(200):
                if len(runs) >= 3:
                    break
                await asyncio.sleep(0.01)
            assert len(runs) == 3
            assert st.restarts == 2
            assert sup.metrics.crashes.with_labels("t", "loop") \
                .value == 2
            assert sup.metrics.restarts.with_labels("t", "loop") \
                .value == 2
            await sup.stop()
        run(go())

    def test_restart_budget_exhaustion(self):
        async def go():
            reg = libmetrics.Registry()
            sup = Supervisor("t", metrics=SupMetrics(reg))
            runs = []
            gaveup = []

            async def always_crash():
                runs.append(1)
                raise RuntimeError("persistent")

            st = sup.spawn(
                always_crash, name="crashy", kind="crashy",
                policy=RestartPolicy(max_restarts=3, window_s=1e9,
                                     backoff_base_s=0.001,
                                     backoff_max_s=0.002, jitter=0.0),
                on_giveup=lambda t, e: gaveup.append(str(e)))
            await st.wait()
            # initial run + 3 restarts, then abandon
            assert len(runs) == 4
            assert st.gave_up
            assert gaveup == ["persistent"]
            assert sup.metrics.giveups.with_labels("t", "crashy") \
                .value == 1
            assert sup.metrics.restarts.with_labels("t", "crashy") \
                .value == 3
            await sup.stop()
        run(go())

    def test_backoff_schedule_deterministic_under_fake_clock(self):
        async def go():
            import random
            sleeps = []
            clock = [0.0]

            async def fake_sleep(d):
                sleeps.append(d)
                clock[0] += d

            sup = Supervisor("t", monotonic=lambda: clock[0],
                             sleep=fake_sleep,
                             rng=random.Random(42))
            runs = []

            async def always_crash():
                runs.append(1)
                raise RuntimeError("x")

            policy = RestartPolicy(max_restarts=4, window_s=1e9,
                                   backoff_base_s=0.1,
                                   backoff_max_s=0.5, jitter=0.0)
            st = sup.spawn(always_crash, policy=policy)
            await st.wait()
            # capped exponential: 0.1, 0.2, 0.4, 0.5 — exact with
            # jitter=0, reproducible with a seeded rng otherwise
            assert sleeps == [0.1, 0.2, 0.4, 0.5]

            # seeded jitter is deterministic: two supervisors with the
            # same seed produce the same schedule
            def sched(seed):
                s = Supervisor("t", monotonic=lambda: 0.0,
                               rng=random.Random(seed))
                p = RestartPolicy(jitter=0.2)
                return [s.backoff(n, p) for n in range(1, 5)]
            assert sched(7) == sched(7)
            assert sched(7) != sched(8)
        run(go())

    def test_cancel_stops_without_restart(self):
        async def go():
            sup = Supervisor("t")
            started = []

            async def loop():
                started.append(1)
                await asyncio.Event().wait()

            st = sup.spawn(loop, name="loop")
            await asyncio.sleep(0.01)
            st.cancel()
            await st.wait()
            await asyncio.sleep(0.02)
            assert len(started) == 1
            assert not st.gave_up
        run(go())

    def test_normal_return_ends_supervision(self):
        async def go():
            sup = Supervisor("t")
            runs = []

            async def one_shot():
                runs.append(1)

            st = sup.spawn(one_shot, name="once")
            await st.wait()
            await asyncio.sleep(0.02)
            assert runs == [1]
            assert st.restarts == 0
        run(go())


# ---------------------------------------------------------------------
# Circuit breaker

class TestCircuitBreaker:
    def _mk(self, **kw):
        self.clock = [0.0]
        reg = libmetrics.Registry()
        br = CircuitBreaker("test", monotonic=lambda: self.clock[0],
                            metrics=BreakerMetrics(reg), **kw)
        return br, reg

    def test_threshold_opens_then_half_open_probe_success(self):
        br, _ = self._mk(failure_threshold=2, reset_timeout_s=10.0)
        assert br.state == CLOSED and br.allow()
        br.record_failure()
        assert br.state == CLOSED          # below threshold
        br.record_failure()
        assert br.state == OPEN
        assert not br.allow()              # still cooling down
        self.clock[0] = 11.0
        assert br.allow()                  # the single probe
        assert br.state == HALF_OPEN
        assert not br.allow()              # probe in flight
        br.record_success()
        assert br.state == CLOSED and br.allow()

    def test_half_open_probe_failure_reopens(self):
        br, _ = self._mk(failure_threshold=1, reset_timeout_s=10.0)
        br.record_failure()
        assert br.state == OPEN
        self.clock[0] = 10.0
        assert br.allow()
        br.record_failure()
        assert br.state == OPEN
        assert not br.allow()              # new cooldown from t=10
        self.clock[0] = 19.9
        assert not br.allow()
        self.clock[0] = 20.1
        assert br.allow()

    def test_latched_open_never_reprobes(self):
        br, reg = self._mk(failure_threshold=1, reset_timeout_s=1.0)
        br.record_failure(latch=True)
        assert br.state == LATCHED_OPEN
        self.clock[0] = 1e12               # any amount of time later
        assert not br.allow()
        br.record_success()                # cannot resurrect it
        assert br.state == LATCHED_OPEN
        assert 'breaker="test"' in reg.render()
        assert "cometbft_breaker_state" in reg.render()


# ---------------------------------------------------------------------
# TPU dispatch behind the breaker (crypto/batch.py)

def _pretend_tpu(monkeypatch):
    """set_backend("tpu") refuses a host without a TPU; these tests
    inject the kernel fault themselves, so the device gate is told
    one is there."""
    from cometbft_tpu.ops import device
    monkeypatch.setattr(device, "_device",
                        device.Device("tpu", "fake", 1, ""))


class TestTpuDispatchBreaker:
    def test_failing_kernel_attempted_at_most_once(self, monkeypatch):
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.ops import ed25519_jax as ej

        _pretend_tpu(monkeypatch)
        attempts = []

        def exploding_verify(items):
            attempts.append(len(items))
            raise RuntimeError("Mosaic lowering failed on this "
                               "platform")

        monkeypatch.setattr(ej, "verify_batch", exploding_verify)
        crypto_batch.reset_tpu_breaker()
        try:
            crypto_batch.set_backend("tpu")
            pk = ed25519.gen_priv_key()
            pub = pk.pub_key()
            for round_ in range(3):     # three batches
                bv = crypto_batch.create_batch_verifier(pub)
                for m in (b"a", b"b"):
                    bv.add(pub, m, pk.sign(m))
                ok, mask = bv.verify()
                # the CPU fallback still yields correct verdicts
                assert ok and list(mask) == [True, True]
            # the failing kernel was dispatched exactly once: the
            # breaker latched open on the non-transient error
            assert len(attempts) == 1
            assert crypto_batch.tpu_breaker().state == LATCHED_OPEN
            # state is visible on the process-global registry
            text = libmetrics.DEFAULT.render()
            assert 'cometbft_breaker_state{breaker='\
                   '"crypto_tpu_kernel"} 3' in text
        finally:
            crypto_batch.set_backend("cpu")
            crypto_batch.reset_tpu_breaker()

    def test_fallback_logs_the_caught_exception_once_per_latch(
            self, monkeypatch, crypto_log):
        """The fallback is loud: one log record names the exception's
        type and message when it opens the breaker; later batches
        never reach the kernel, so they add none."""
        import logging

        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.ops import ed25519_jax as ej

        _pretend_tpu(monkeypatch)

        def exploding_verify(items):
            raise NotImplementedError(
                "Mosaic failed to compile TPU kernel: unsupported "
                "unaligned sublane slice")

        monkeypatch.setattr(ej, "verify_batch", exploding_verify)
        crypto_batch.reset_tpu_breaker()
        try:
            crypto_batch.set_backend("tpu")
            pk = ed25519.gen_priv_key()
            pub = pk.pub_key()
            for _ in range(3):
                bv = crypto_batch.create_batch_verifier(pub)
                for m in (b"a", b"b"):
                    bv.add(pub, m, pk.sign(m))
                ok, mask = bv.verify()
                assert ok and list(mask) == [True, True]
            errors = [r for r in crypto_log
                      if r.levelno >= logging.ERROR]
            assert len(errors) == 1
            text = errors[0].getMessage()
            assert "NotImplementedError" in text
            assert "unaligned sublane slice" in text
            assert "breaker=latched_open" in text
            assert errors[0].exc_info is not None     # traceback kept
        finally:
            crypto_batch.set_backend("cpu")
            crypto_batch.reset_tpu_breaker()

    def test_transient_fault_reprobes_after_cooldown(self, monkeypatch):
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.ops import ed25519_jax as ej

        _pretend_tpu(monkeypatch)
        attempts = []

        def flaky_verify(items):
            attempts.append(1)
            if len(attempts) == 1:
                raise ConnectionError("device connection reset")
            return True, [True] * len(items)

        monkeypatch.setattr(ej, "verify_batch", flaky_verify)
        crypto_batch.reset_tpu_breaker()
        try:
            crypto_batch.set_backend("tpu")
            clock = [0.0]
            br = crypto_batch.tpu_breaker()
            br._monotonic = lambda: clock[0]
            pk = ed25519.gen_priv_key()
            pub = pk.pub_key()

            def batch_once():
                bv = crypto_batch.create_batch_verifier(pub)
                bv.add(pub, b"m", pk.sign(b"m"))
                bv.add(pub, b"n", pk.sign(b"n"))
                return bv.verify()

            batch_once()                   # transient failure -> OPEN
            assert br.state == OPEN
            batch_once()                   # cooling down: no attempt
            assert len(attempts) == 1
            clock[0] = 1e6                 # past the reset timeout
            ok, mask = batch_once()        # half-open probe succeeds
            assert ok and br.state == CLOSED
            assert len(attempts) == 2
        finally:
            crypto_batch.set_backend("cpu")
            crypto_batch.reset_tpu_breaker()


# ---------------------------------------------------------------------
# ABCI deadlines

class TestABCIDeadlines:
    def test_wedged_call_times_out(self):
        from cometbft_tpu.abci.client import (
            ABCITimeoutError, DeadlineClient,
        )

        class WedgedApp:
            async def info(self, req):
                await asyncio.sleep(3600)

        async def go():
            cli = DeadlineClient(WedgedApp(), default_timeout_s=0.05)
            with pytest.raises(ABCITimeoutError):
                await cli.info(None)
        run(go())

    def test_transient_error_retried_read_only_call(self):
        from cometbft_tpu.abci.client import DeadlineClient

        class FlakyApp:
            def __init__(self):
                self.calls = 0

            async def info(self, req):
                self.calls += 1
                if self.calls < 3:
                    raise ConnectionResetError("transport hiccup")
                return "ok"

            async def finalize_block(self, req):
                self.calls += 1
                raise ConnectionResetError("transport hiccup")

        async def go():
            app = FlakyApp()
            cli = DeadlineClient(app, default_timeout_s=1.0,
                                 retries=2, retry_backoff_s=0.001)
            assert await cli.info(None) == "ok"
            assert app.calls == 3
            # state-mutating calls get exactly one attempt
            app.calls = 0
            with pytest.raises(ConnectionResetError):
                await cli.finalize_block(None)
            assert app.calls == 1
        run(go())

    def test_slow_methods_get_wider_budget(self):
        from cometbft_tpu.abci.client import DeadlineClient

        cli = DeadlineClient(object(), default_timeout_s=10.0)
        assert cli.timeout_for("query") == 10.0
        assert cli.timeout_for("finalize_block") == 60.0


# ---------------------------------------------------------------------
# FuzzedConnection: reorder + duplicate

class _Sink:
    def __init__(self):
        self.frames = []

    async def write_msg(self, data):
        self.frames.append(data)

    async def read_msg(self):
        raise NotImplementedError

    def close(self):
        pass


class TestFuzzReorderDuplicate:
    def test_reorder_and_duplicate_counted_and_seeded(self):
        from cometbft_tpu.p2p.fuzz import FuzzConfig, FuzzedConnection

        async def feed(seed):
            sink = _Sink()
            fz = FuzzedConnection(sink, FuzzConfig(
                prob_reorder=0.3, prob_duplicate=0.3, seed=seed))
            for i in range(200):
                await fz.write_msg(b"f%03d" % i)
            return fz, sink

        async def go():
            fz, sink = await feed(seed=99)
            assert fz.reordered > 0 and fz.duplicated > 0
            # conservation: every frame either shipped (plus dups) or
            # is the single held-back frame
            held = 1 if fz._held is not None else 0
            assert len(sink.frames) == 200 + fz.duplicated - held
            # reordering actually swaps adjacent frames
            assert sink.frames != sorted(sink.frames) or fz.reordered == 0

            # determinism: the same seed produces the same schedule
            fz2, sink2 = await feed(seed=99)
            assert (fz2.reordered, fz2.duplicated) == \
                (fz.reordered, fz.duplicated)
            assert sink2.frames == sink.frames
            fz3, sink3 = await feed(seed=100)
            assert sink3.frames != sink.frames
        run(go())

    def test_gated_draws_preserve_legacy_schedules(self):
        """With the new probabilities at 0, the seeded drop/delay
        schedule is identical to the pre-extension behavior (no extra
        RNG draws)."""
        from cometbft_tpu.p2p.fuzz import FuzzConfig, FuzzedConnection

        async def go():
            sink = _Sink()
            fz = FuzzedConnection(sink, FuzzConfig(
                prob_drop_write=0.5, seed=42))
            for i in range(100):
                await fz.write_msg(b"x%02d" % i)
            assert fz.reordered == 0 and fz.duplicated == 0
            assert len(sink.frames) == 100 - fz.dropped
        run(go())


# ---------------------------------------------------------------------
# Metrics memo bound (ADVICE r5 #2)

class TestMetricsMemoBound:
    def test_memo_bounded_and_str_only(self):
        from cometbft_tpu.libs.metrics import _MEMO_MAX, Registry

        reg = Registry()
        c = reg.counter("t", "total", "x", labels=("peer",))
        for i in range(_MEMO_MAX + 500):
            c.with_labels(f"peer-{i}").inc()
        assert len(c._memo) <= _MEMO_MAX
        # children still exist (bounded memo, not bounded data)
        assert len(c._children) == _MEMO_MAX + 500
        # non-str values resolve to the same child but are not memoized
        g = reg.gauge("t", "g", "x", labels=("n",))
        child_int = g.with_labels(1)
        child_str = g.with_labels("1")
        assert child_int is child_str
        assert (1,) not in g._memo


# ---------------------------------------------------------------------
# Reactor loops are supervisor-owned

class TestReactorSupervision:
    def test_evidence_broadcast_crash_restarts(self):
        from cometbft_tpu.evidence.reactor import EvidenceReactor

        class ExplodingPool:
            def __init__(self):
                self.calls = 0
                self.version = 0

            def all_pending(self):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("boom")
                return []

        class FakePeer:
            id = "feedfacefeedface"

            def send(self, chan, msg):
                return True

        async def go():
            pool = ExplodingPool()
            # version != seen_version so the loop calls all_pending
            pool.version = 1
            r = EvidenceReactor(pool)
            await r.add_peer(FakePeer())
            for _ in range(100):
                if pool.calls >= 2:
                    break
                await asyncio.sleep(0.02)
            sup = r.supervisor
            assert sup.metrics.crashes.with_labels(
                "evidence", "evidence_broadcast").value == 1
            assert sup.metrics.restarts.with_labels(
                "evidence", "evidence_broadcast").value == 1
            assert pool.calls >= 2      # the loop came back
            await r.remove_peer(FakePeer(), "done")
            await sup.stop()
        run(go())
