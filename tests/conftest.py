"""Test config: the CPU JAX platform, eight virtual devices.

No accelerator is needed or used by the tests: tier-1 runs with
JAX_PLATFORMS=cpu, and the platform is pinned again here in-process so
a bare `pytest` on a chip host cannot take the chip either.  Sharding
correctness is validated on a virtual 8-device CPU mesh (the driver
separately dry-runs the multi-chip path via
__graft_entry__.dryrun_multichip); the chip itself is exercised by
chip_smoke.py, not by pytest.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Host-protocol tests exercise the CPU crypto path; kernel tests import
# ops.ed25519_jax directly (and run it on the virtual CPU devices).
from cometbft_tpu.crypto import batch  # noqa: E402

if not os.environ.get("COMETBFT_TPU_CRYPTO_BACKEND"):
    batch.set_backend("cpu")


# ---------------------------------------------------------------------------
# Per-test wall-clock timeouts (VERDICT r4 #8: one hung net must not
# mask the whole tier).  SIGALRM raises inside the test — including
# inside asyncio.run — so a wedged event loop still fails fast with a
# traceback instead of eating the session.  Budgets are generous (the
# box has one CPU and kernel tests pay a 60-110 s cold compile);
# override per test with @pytest.mark.timeout_s(N).

import signal

import pytest

_DEFAULT_TIMEOUT_S = 300
_SLOW_TIMEOUT_S = 600
_KERNEL_TIMEOUT_S = 900


class _TestTimeout(Exception):
    pass


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    limit = _DEFAULT_TIMEOUT_S
    if request.node.get_closest_marker("slow"):
        limit = _SLOW_TIMEOUT_S
    if request.node.get_closest_marker("kernel"):
        limit = _KERNEL_TIMEOUT_S
    override = request.node.get_closest_marker("timeout_s")
    if override and override.args:
        limit = override.args[0]

    def on_alarm(signum, frame):
        raise _TestTimeout(
            f"test exceeded its {limit}s wall-clock budget")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def crypto_log():
    """LogRecords of the cometbft.crypto logger (the cometbft root does
    not propagate, so caplog alone never sees them)."""
    import logging

    from cometbft_tpu.libs.log import new_logger
    records = []

    class Grab(logging.Handler):
        def emit(self, record):
            records.append(record)

    new_logger("crypto")        # configures the cometbft root once
    lg = logging.getLogger("cometbft.crypto")
    grab = Grab(level=logging.DEBUG)
    lg.addHandler(grab)
    try:
        yield records
    finally:
        lg.removeHandler(grab)
