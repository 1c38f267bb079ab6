"""The device gate (ops/device.py) and everything that asks it.

One module answers "is there a chip"; backend selection resolves once
and loudly; the compile cache is placed from outside or at one fixed
path; the measurement commands refuse to run without a TPU.  All on
the CPU: the gate is told what to report where a TPU is needed.
"""
import ast
import os
import re
import subprocess
import sys

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.ops import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake(platform: str) -> device.Device:
    return device.Device(platform, "fake-kind", 1, "/nowhere")


@pytest.fixture
def unresolved(monkeypatch):
    """Backend selection as a fresh process sees it: nothing set,
    nothing resolved, no environment choice."""
    monkeypatch.setattr(crypto_batch, "_backend", None)
    monkeypatch.setattr(crypto_batch, "_resolved", None)
    monkeypatch.delenv("COMETBFT_TPU_CRYPTO_BACKEND", raising=False)


class TestGate:
    def test_allowlist_is_tpu_only(self):
        assert device.TPU_PLATFORMS == frozenset({"tpu"})
        assert _fake("tpu").is_tpu
        for other in ("cpu", "gpu", "cuda", "rocm", "METAL", "TPU"):
            assert not _fake(other).is_tpu

    def test_probe_reports_what_jax_reports(self):
        import jax
        dev = device.probe()
        d0 = jax.devices()[0]
        assert (dev.platform, dev.kind, dev.count) == \
            (d0.platform, d0.device_kind, len(jax.devices()))
        assert dev.cache_dir == jax.config.jax_compilation_cache_dir
        assert device.probe() is dev        # resolved once

    def test_require_tpu_names_the_platform_that_answered(self):
        with pytest.raises(device.NoTpuError, match="'cpu'"):
            device.require_tpu()


class TestBackendSelection:
    def test_explicit_tpu_without_chip_raises_at_selection(self):
        before = crypto_batch.get_backend()
        with pytest.raises(device.NoTpuError):
            crypto_batch.set_backend("tpu")
        assert crypto_batch.get_backend() == before

    def test_env_tpu_without_chip_raises_at_first_ask(
            self, unresolved, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
        with pytest.raises(device.NoTpuError):
            crypto_batch.get_backend()

    def test_auto_resolves_once_and_is_logged(
            self, unresolved, monkeypatch, crypto_log):
        asks = []
        real = device.probe
        monkeypatch.setattr(
            device, "probe", lambda: asks.append(1) or real())
        assert crypto_batch.get_backend() == "cpu"
        assert crypto_batch.get_backend() == "cpu"
        assert len(asks) == 1
        found = [r.getMessage() for r in crypto_log
                 if "backend resolved" in r.getMessage()]
        assert len(found) == 1
        assert "backend=cpu" in found[0] and "platform=cpu" in found[0]
        assert "compile_cache=" in found[0]

    def test_auto_takes_the_tpu_and_never_flips(
            self, unresolved, monkeypatch):
        monkeypatch.setattr(device, "_device", _fake("tpu"))
        assert crypto_batch.get_backend() == "tpu"
        monkeypatch.setattr(device, "_device", _fake("cpu"))
        assert crypto_batch.get_backend() == "tpu"

    def test_auto_ignores_accelerators_off_the_allowlist(
            self, unresolved, monkeypatch):
        monkeypatch.setattr(device, "_device", _fake("gpu"))
        assert crypto_batch.get_backend() == "cpu"

    def test_a_backend_that_fails_to_start_is_not_cpu(
            self, unresolved, monkeypatch):
        def broken():
            raise RuntimeError("Unable to initialize backend 'tpu'")
        monkeypatch.setattr(device, "probe", broken)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            crypto_batch.get_backend()


def _run(code: str, **env) -> subprocess.CompletedProcess:
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, "-c", code], env=full,
                          capture_output=True, text=True, timeout=120)


class TestCachePlacement:
    CODE = ("import jax; from cometbft_tpu.ops import device; "
            "d = device.probe(); "
            "print(d.cache_dir); "
            "print(jax.config.jax_compilation_cache_dir)")

    def test_env_set_is_left_untouched(self, tmp_path):
        want = str(tmp_path / "outside")
        p = _run(self.CODE, JAX_COMPILATION_CACHE_DIR=want)
        assert p.returncode == 0, p.stderr[-2000:]
        assert p.stdout.split() == [want, want]

    def test_unset_is_the_fixed_in_checkout_path(self):
        p = _run(self.CODE)
        assert p.returncode == 0, p.stderr[-2000:]
        want = os.path.join(REPO, ".jax_cache")
        assert p.stdout.split() == [want, want]
        assert device.default_cache_dir() == want


class TestCpuNodeNeverImportsJax:
    def test_cpu_backend_verifies_without_jax(self):
        p = _run(
            "import sys\n"
            "from cometbft_tpu.crypto import batch, ed25519\n"
            "from cometbft_tpu.ops import device\n"
            "assert batch.get_backend() == 'cpu'\n"
            "sk = ed25519.gen_priv_key()\n"
            "bv = batch.create_batch_verifier(sk.pub_key())\n"
            "for m in (b'a', b'b'):\n"
            "    bv.add(sk.pub_key(), m, sk.sign(m))\n"
            "assert bv.verify()[0]\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n",
            COMETBFT_TPU_CRYPTO_BACKEND="cpu")
        assert p.returncode == 0, p.stderr[-2000:]


def _imports(path: str):
    """Every module a file imports, anywhere in it (function bodies
    too), as absolute dotted names; ``from m import a`` yields m and
    m.a."""
    package = path.split("/")[:-1]
    with open(os.path.join(REPO, path)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] \
                if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            yield mod
            yield from (f"{mod}.{a.name}" for a in node.names)


class TestTheArrowsPointOneWay:
    """crypto/batch (the seam) reaches ops/ed25519_jax (the device
    dispatch) lazily; both read the geometry of a dispatch in
    crypto/pipeline, which is jax-free and imports neither."""

    @pytest.mark.parametrize("path, forbidden", [
        ("cometbft_tpu/ops/ed25519_jax.py",
         ("cometbft_tpu.crypto.batch",)),
        ("cometbft_tpu/crypto/pipeline.py",
         ("cometbft_tpu.crypto.batch", "cometbft_tpu.ops", "jax")),
    ])
    def test_no_import_upward(self, path, forbidden):
        found = sorted(set(_imports(path)))
        assert "cometbft_tpu.libs.tracing" in found     # it resolves
        upward = [m for m in found for f in forbidden
                  if m == f or m.startswith(f + ".")]
        assert upward == []


class TestEnvironmentSwitches:
    # the whole list; it only ratchets down (ROADMAP D6)
    NAMES = {"COMETBFT_TPU_" + n for n in (
        "CRYPTO_BACKEND", "KERNEL", "NATIVE", "DUMP_DIR",
        "BREAKER_RESET_S", "MSM_THREADS")}

    def test_the_program_reads_these_and_no_other(self):
        found = set()
        for top in ("cometbft_tpu", "native"):
            for root, dirs, files in os.walk(os.path.join(REPO, top)):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for name in files:
                    if not name.endswith((".py", ".cpp", ".hpp")):
                        continue
                    with open(os.path.join(root, name)) as f:
                        found.update(re.findall(
                            r"COMETBFT_TPU_[A-Z0-9_]+", f.read()))
        assert found == self.NAMES


class TestMeasurementCommandsRefuseWithoutAChip:
    def _refused(self, script: str, *args):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.join(REPO, script), *args],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=REPO)

    def test_chip_smoke_fails_at_the_device_stage(self):
        p = self._refused("chip_smoke.py")
        assert p.returncode != 0
        assert "NoTpuError" in p.stderr
        # it said what it found, then stopped before any other stage
        assert "platform: cpu" in p.stdout
        assert "[stage device] ok" not in p.stdout
        assert "[stage native]" not in p.stdout
        assert '"ok"' not in p.stdout and "{" not in p.stdout

    def test_chip_smoke_last_line_is_ok_and_device_only(self):
        # the driver reads the last stdout line: exactly these keys
        import importlib.util
        import json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        smoke = cs.Smoke(seed=0, sizes=cs.TINY, rehearsal=False)
        smoke.device = {"platform": "tpu", "kind": "TPU v5 lite",
                        "count": 1}
        res = json.loads(json.dumps(smoke.result()))
        assert res == {"ok": True, "device": smoke.device}
        summary = smoke.summary()
        assert list(summary)[-1] == "claim" and summary["claim"] is None
        assert "ok" not in summary
        assert cs.Smoke(0, cs.TINY, rehearsal=True).result()["ok"] is False

    def test_bench_prints_no_metric(self):
        p = self._refused("bench.py")
        assert p.returncode != 0
        assert "NoTpuError" in p.stderr
        assert p.stdout.strip() == ""

    def test_microbench_suite_needs_the_chip(self):
        from cometbft_tpu.ops import microbench
        with pytest.raises(device.NoTpuError):
            microbench.run_suite()


class TestWarmupCoversTheDispatchShapes:
    """warmup() and the live dispatch go through one launch function,
    at the shapes verify_batch really pads to — so a node that warmed
    its validator-set size does not compile on its first commit."""

    @pytest.fixture
    def launched(self, monkeypatch):
        import numpy as np
        from cometbft_tpu.ops import ed25519_jax as ej
        shapes = []

        def fake_launch(wire, **kw):
            assert wire.shape[1] == ej.WIRE_LANE_BYTES
            shapes.append((wire.shape[0], kw["choice"]))
            return np.ones(wire.shape[0], bool)

        monkeypatch.setattr(ej, "_launch", fake_launch)
        monkeypatch.setattr(ej, "_force",
                            lambda dev, sp=None: np.asarray(dev))
        monkeypatch.setenv("COMETBFT_TPU_KERNEL", "pallas")
        ej._warmup_bucket.cache_clear()
        yield shapes
        ej._warmup_bucket.cache_clear()

    def test_shapes_for_the_three_deployments(self, launched):
        from cometbft_tpu.ops import ed25519_jax as ej
        ej.warmup(4)            # live net: one Pallas block
        ej.warmup(175)          # QA size: the 1024 bucket
        ej.warmup(10_000)       # north star: 1,024-lane tiles, the
        #                         shape the QA size has warmed
        ej.warmup(1_025)
        assert [m for m, _ in launched] == [128, 1024]
        assert {k for _, k in launched} == {"pallas"}

    def test_node_warms_commit_light_and_vote_sizes(self, monkeypatch):
        from cometbft_tpu.crypto import _native_loader
        from cometbft_tpu.node import node as node_mod
        from cometbft_tpu.ops import ed25519_jax as ej
        asked = []
        monkeypatch.setattr(ej, "warmup", asked.append)
        monkeypatch.setattr(_native_loader, "load",
                            lambda allow_build=True: object())
        node_mod.warm_device_path(175)
        assert asked == [1, 117, 175]


class TestForceRecordsWhereTheMaskCameFrom:
    def test_platform_and_device_count_on_the_span(self):
        import jax.numpy as jnp
        from cometbft_tpu.ops import ed25519_jax as ej

        class Span:
            attrs = {}

            def note(self, **kw):
                self.attrs.update(kw)

        sp = Span()
        out = ej._force(jnp.ones(4, bool), sp)
        assert out.tolist() == [True] * 4
        assert sp.attrs == {"platform": "cpu", "devices": 1}
