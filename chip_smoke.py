#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the verification path still
starts, and is right, on the chip.

ONE process (a chip belongs to one process; nothing here spawns a
child that imports JAX) drives the main path once through the entry
points a user calls, at the validator-set sizes operators run, on
whatever backend the program's own ``auto`` resolves — no
COMETBFT_TPU_* variable is set.  Stages, each of which must pass for
exit 0; no stage's exception is caught and carried past:

  device    JAX's default platform must be ``tpu``, else non-zero exit
            before anything else
  native    build the C++ host prep from native/*.cpp
  seam-175  the CometBFT QA validator set (175, equal power): one
            commit through verify_commit and verify_commit_light,
            forged variants named by index, the per-lane mask of the
            BatchVerifier seam against the golden model
  seam-10k  the same at the north-star 10,000 validators (the tiled,
            overlapped dispatch)
  light-1k  light.Client.verify_to_height over a 1,000-validator chain
            whose set changes every height: a refused jump, a
            bisection, two verified hops; then a forged target
  net-4     a live net: four validators + a late-joining full node on
            the kvstore app, 1 kB txs over RPC, cross-node invariants,
            acknowledged writes read back from another node
  verdict   the device did the work: every ed25519 batch_verify span
            says backend=tpu with no fallback, the breaker is closed,
            the kernel was pallas and its output came off a tpu device

All data is made from --seed.  The last two stdout lines are JSON: the
summary ({"seed": ..., "stages": ..., "verdict": ..., "claim": null})
and then, last, the result object with exactly these keys:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}},
the device as JAX reports it.  Wall and compile seconds printed per
stage are set-up facts, not results.

``--rehearsal`` is for debugging THIS SCRIPT on a CPU before chip time
is spent: tiny sizes, the Pallas kernel in interpret mode.  It says
"REHEARSAL — not a chip result", never prints ok: true and never
exits 0 (4 = the rehearsal's stages passed).
"""
from __future__ import annotations

import argparse
import asyncio
import base64
import json
import os
import random
import sys
import tempfile
import threading
import time
from typing import NamedTuple

REHEARSAL_BANNER = "REHEARSAL — not a chip result"
REHEARSAL_EXIT = 4

# every jitted function that IS the verification kernel carries this
# in its name: _pallas_verify_packed, _pallas_batch_verify_packed,
# _verify_packed, and the shard_map'ed sharded_<kernel>_verify
# (parallel/mesh.py)
KERNEL_MARK = "verify"


class Sizes(NamedTuple):
    qa_vals: int            # CometBFT QA v1: 175 validators
    star_vals: int          # BASELINE.json north star: 10,000
    light_vals: int         # BASELINE.json config #3: 1,000
    light_churn: int        # keys that change a height (light-1k: 10)
    light_heights: int      # twice the reach of one hop, plus one
    net_vals: int           # BASELINE.json config #1: 4 validators
    net_height: int
    net_timeout_s: float
    writes: int             # broadcast_tx_commit calls read back
    honest_sample: int      # honest lanes checked against the golden


REAL = Sizes(175, 10_000, 1000, 10, 129, 4, 10, 120.0, 5, 512)
TINY = Sizes(24, 40, 10, 1, 9, 2, 4, 240.0, 2, 8)
TX_SIZE = 1024              # upstream QA's transaction size


class CompileLog:
    """Every backend compile request JAX makes while the smoke runs:
    (jitted function name, seconds, served by the persistent cache)."""

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


class Smoke:
    def __init__(self, seed: int, sizes: Sizes, rehearsal: bool):
        self.seed = seed
        self.sizes = sizes
        self.rehearsal = rehearsal
        self.compiles = CompileLog()
        self.stages: dict[str, dict] = {}
        self.spans: dict[str, list[dict]] = {}
        self.device = None
        self.versions: dict[str, str] = {}
        self.verdict: dict = {}
        self._warm_mark = None
        self._laps: dict[str, float] = {}
        self._lap_t = 0.0

    # -- stage plumbing ----------------------------------------------
    def run(self, name: str, fn) -> None:
        from cometbft_tpu.libs import tracing
        print(f"[stage {name}] start", flush=True)
        tracing.clear()
        mark = len(self.compiles.events)
        self._warm_mark = None
        self._laps = {}
        t0 = self._lap_t = time.perf_counter()
        fn()                    # a failing stage raises: non-zero exit
        wall = time.perf_counter() - t0
        events = self.compiles.events[mark:]
        after = self.compiles.events[self._warm_mark:] \
            if self._warm_mark is not None else []
        spans = self.spans[name] = tracing.snapshot(
            category=tracing.CRYPTO)
        rep = {
            "wall_s": round(wall, 3),
            "laps_s": self._laps,
            # backend compile requests: seconds, how many really
            # compiled, how many the persistent cache served
            "compile_s": round(sum(s for _, s, _ in events), 3),
            "compiled": sum(1 for _, _, hit in events if not hit),
            "cache_hits": sum(1 for _, _, hit in events if hit),
            "kernel_compiled": sum(
                1 for f, _, hit in events
                if not hit and KERNEL_MARK in f),
            # first dispatch of a shape, trace + lower + compile (or
            # cache load) + one run, by padded lane count
            "shape_setup_s": {
                str((ev.get("attrs") or {}).get("bucket")):
                    round(ev["dur_ns"] / 1e9, 3)
                for ev in spans
                if ev["name"] == "kernel_compile"
                or (ev["name"] == "kernel_execute"
                    and not (ev.get("attrs") or {}).get("warm"))},
            "compiles_after_warmup": len(after),
            "after_warmup_funs": sorted({f for f, _, _ in after}),
        }
        self.stages[name] = rep
        print(f"[stage {name}] ok " + " ".join(
            f"{k}={v}" for k, v in rep.items()), flush=True)

    def lap(self, label: str) -> None:
        """Seconds since the stage's previous lap, under ``label``."""
        now = time.perf_counter()
        self._laps[label] = round(now - self._lap_t, 3)
        self._lap_t = now

    def warmed(self) -> None:
        """Everything compiled from here to the end of the stage is a
        compilation after warm-up."""
        self._warm_mark = len(self.compiles.events)
        self.lap("warmup")

    def rng(self, tag: str) -> random.Random:
        return random.Random(f"{tag}-{self.seed}")

    # -- stages --------------------------------------------------------
    def stage_device(self) -> None:
        import jax
        import jaxlib

        from cometbft_tpu.ops import device
        dev = device.probe()
        devs = jax.devices()
        # the device as JAX reports it, not as the gate remembers it
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if self.device != dev.summary():
            raise RuntimeError(f"device gate {dev} disagrees with "
                               f"jax.devices(): {self.device}")
        try:
            import libtpu
            libtpu_version = getattr(libtpu, "__version__", "unknown")
        except ImportError:
            libtpu_version = "not installed"
        self.versions = {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu_version}
        env_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        print(f"[device] platform: {dev.platform}  device_kind: "
              f"{dev.kind}  count: {dev.count}")
        print("[device] " + "  ".join(
            f"{k} {v}" for k, v in self.versions.items()))
        print(f"[device] compile cache: {dev.cache_dir} "
              f"(JAX_COMPILATION_CACHE_DIR "
              f"{'=' + env_cache if env_cache else 'unset'})",
              flush=True)
        if not self.rehearsal:
            device.require_tpu()

    def stage_native(self) -> None:
        from cometbft_tpu.crypto import _native_loader
        native = _native_loader.load(allow_build=True)
        if native is None or not hasattr(native, "ed25519_prep"):
            raise RuntimeError(
                "native module did not build from native/*.cpp")

    def stage_seam(self, n: int) -> None:
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.node.node import warm_device_path
        from cometbft_tpu.tools import benchmarks
        from cometbft_tpu.types.validation import (
            VerificationError, verify_commit, verify_commit_light,
        )

        backend = crypto_batch.get_backend()
        if backend != "tpu":
            raise RuntimeError(f"auto resolved to {backend!r}")
        warm_device_path(n)     # what a node does before consensus
        self.warmed()

        rng = self.rng(f"forge-{n}")
        heights = iter(range(1, 1000))

        def fresh(forged=()):
            """(chain_id, vset, block_id, height, commit) for a commit
            no memo has seen, the signatures at the ``forged`` indices
            flipped in one bit of R."""
            chain_id, vset, bid, commit = benchmarks.seeded_commit(
                n, self.seed, height=next(heights))
            for idx in forged:
                cs = commit.signatures[idx]
                cs.signature = _flip_bit(rng, cs.signature, 0, 32)
            return chain_id, vset, bid, commit.height, commit

        def must_name(fn, args, idx: int) -> None:
            try:
                fn(*args)
            except VerificationError as e:
                if f"(#{idx})" not in str(e):
                    raise RuntimeError(
                        f"{fn.__name__} named the wrong signature: "
                        f"want #{idx}, got: {str(e)[:80]}") from e
            else:
                raise RuntimeError(
                    f"{fn.__name__} accepted a commit forged at #{idx}")

        honest = fresh()
        verify_commit(*honest)
        verify_commit_light(*fresh())
        self.lap("honest")

        # equal powers: the light walk stops once `mark` signatures
        # are tallied, so a forgery at or past it is never looked at
        vset = honest[1]
        power = vset.validators[0].voting_power
        mark = vset.total_voting_power() * 2 // 3 // power + 1
        below, above = rng.randrange(mark), rng.randrange(mark, n)
        must_name(verify_commit, fresh({above}), above)
        verify_commit_light(*fresh({above}))
        must_name(verify_commit, fresh({below}), below)
        must_name(verify_commit_light, fresh({below}), below)
        must_name(verify_commit, fresh({below, above}), below)
        print(f"[seam-{n}] verify_commit / verify_commit_light: honest "
              f"accepted; forged #{below} (below the 2/3 mark {mark}) "
              f"and #{above} (above) named", flush=True)

        self.lap("forged")
        chain_id, vset, _, _, commit = fresh()
        self._check_mask(chain_id, vset, commit)
        self.lap("mask")

    def _check_mask(self, chain_id: str, vset, commit) -> None:
        """The per-lane mask of the BatchVerifier seam against an
        implementation independent of the kernels."""
        from cometbft_tpu.crypto import _ed25519_ref as ref
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import ed25519

        n = vset.size()
        rng = self.rng(f"mask-{n}")
        items = [(vset.validators[i].pub_key.bytes(),
                  commit.vote_sign_bytes(chain_id, i),
                  commit.signatures[i].signature) for i in range(n)]
        special = _edge_lanes(rng, items)
        slots = rng.sample(range(n), len(special))
        for slot, lane in zip(slots, special):
            items[slot] = lane
        honest = sorted(set(range(n)) - set(slots))
        sample = rng.sample(honest,
                            min(self.sizes.honest_sample, len(honest)))

        # no signature cache anywhere near: the verifier sees raw lanes
        bv = crypto_batch.create_batch_verifier(
            vset.validators[0].pub_key)
        for pub, msg, sig in items:
            bv.add(ed25519.Ed25519PubKey(pub), msg, sig)
        ok, mask = bv.verify()
        mask = [bool(g) for g in mask]
        self.lap("mask_verify")

        golden_at = sorted(set(slots) | set(sample))
        bad = [i for i in golden_at
               if mask[i] != ref.verify(*items[i])]
        if bad:
            raise RuntimeError(
                f"mask disagrees with the golden model at lanes "
                f"{bad[:8]} of {len(golden_at)} checked")
        bad = [i for i, (pub, msg, sig) in enumerate(items)
               if mask[i] != ed25519.Ed25519PubKey(
                   pub).verify_signature(msg, sig)]
        if bad:
            raise RuntimeError(
                f"mask disagrees with the per-signature CPU verifier "
                f"at lanes {bad[:8]}")
        if not all(mask[i] for i in honest) or ok != all(mask):
            raise RuntimeError("an honest lane was rejected")
        rejected = sum(1 for i in slots if not mask[i])
        print(f"[seam-{n}] mask: {n} lanes == per-signature CPU "
              f"verifier; {len(golden_at)} lanes == golden model "
              f"({len(special)} forged/ZIP-215 edge lanes, {rejected} "
              f"rejected, {len(special) - rejected} accepted; "
              f"{len(sample)} honest)", flush=True)

    def stage_light(self) -> None:
        """The benchmark's deployment light-1k at a short chain: the
        set at the tip shares no key with the trusted one, so the jump
        is refused and bisected, and the midpoint keeps just over a
        third."""
        from benchmark.reference import skipping
        from cometbft_tpu.db import MemDB
        from cometbft_tpu.light.client import (
            SKIPPING, Client, TrustOptions,
        )
        from cometbft_tpu.light.store import TrustedStore
        from cometbft_tpu.light.verifier import InvalidHeaderError
        from cometbft_tpu.node.node import warm_device_path
        from cometbft_tpu.types.block import LightBlock

        sz = self.sizes
        n, tip = sz.light_vals, sz.light_heights
        warm_device_path(n)
        self.warmed()
        chain = skipping.build_chain("smoke-light", self.seed, n, 10,
                                     sz.light_churn, tip)
        wire = {h: lb.to_proto() for h, lb in chain.blocks.items()}
        self.lap("build")

        class Provider:
            """Every fetch a freshly decoded block."""

            def __init__(self, served: dict):
                self.served = served

            async def light_block(self, height: int) -> LightBlock:
                return LightBlock.from_proto(
                    self.served.get(height) or wire[height])

            def id(self) -> str:
                return "smoke-provider"

        def sync(served: dict) -> TrustedStore:
            provider, store = Provider(served), TrustedStore(MemDB())
            client = Client(
                chain.chain_id,
                TrustOptions(24 * 3600 * 10 ** 9, 1,
                             chain.header_hash(1)),
                provider, [provider], store,
                verification_mode=SKIPPING)

            async def run() -> None:
                await client.initialize(now=chain.now)
                await client.verify_to_height(tip, now=chain.now)
            asyncio.run(run())
            return store

        heights = sync({}).heights()
        if heights != [1, (1 + tip) // 2, tip]:
            raise RuntimeError(f"the store holds {heights}: the jump "
                               f"to {tip} was not bisected once")
        self.lap("hops")
        forged = LightBlock.from_proto(wire[tip])
        cs = forged.signed_header.commit.signatures[0]
        cs.signature = bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
        try:
            sync({tip: forged.to_proto()})
        except InvalidHeaderError as e:
            if "(#0)" not in str(e):
                raise
        else:
            raise RuntimeError("light client accepted a forged header")
        self.lap("forged")
        print(f"[light-1k] {n} validators, {sz.light_churn} changing a "
              f"height: 1 -> {tip} refused and bisected, {heights} "
              f"trusted; a forged target was refused by index",
              flush=True)

    def stage_net(self) -> None:
        from cometbft_tpu.node.node import warm_device_path
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.tools import manifest as mf

        sz = self.sizes
        warm_device_path(sz.net_vals)
        self.warmed()
        m = mf.Manifest(chain_id=f"smoke-{self.seed}",
                        load_tx_size=TX_SIZE)
        for i in range(sz.net_vals):
            m.nodes[f"validator{i:02d}"] = mf.ManifestNode()
        m.nodes["full01"] = mf.ManifestNode(
            mode="full", start_at=sz.net_height // 2)
        acked: list[tuple[str, str, int]] = []

        async def write_and_read_back(nodes) -> None:
            """An acknowledged write is read back — from a node other
            than the one that acknowledged it."""
            names = list(nodes)
            for i in range(sz.writes):
                writer = names[i % len(names)]
                reader = names[(i + 1) % len(names)]
                key = b"smoke-%d-%d" % (self.seed, i)
                val = (b"%064x" % self.rng(f"tx-{i}").getrandbits(256)
                       ) * (TX_SIZE // 64)
                tx = (key + b"=" + val)[:TX_SIZE]
                want = tx.split(b"=", 1)[1]
                res = await HTTPClient(
                    f"http://{nodes[writer]._rpc_server.listen_addr}"
                ).broadcast_tx_commit(tx)
                if res["check_tx"]["code"] or res["tx_result"]["code"]:
                    raise RuntimeError(f"tx {i} rejected: {res}")
                h = int(res["height"])
                deadline = time.monotonic() + 30
                while nodes[reader].height < h:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"{reader} never reached height {h}")
                    await asyncio.sleep(0.05)
                q = await HTTPClient(
                    f"http://{nodes[reader]._rpc_server.listen_addr}"
                ).abci_query("", key)
                got = base64.b64decode(q["response"]["value"])
                if got != want:
                    raise RuntimeError(
                        f"write {i} acknowledged by {writer} at height "
                        f"{h} was not read back from {reader}")
                acked.append((writer, reader, h))

        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as out:
            report = asyncio.run(mf.run_manifest(
                m, out, target_height=sz.net_height,
                timeout_s=sz.net_timeout_s,
                while_up=write_and_read_back))
        if report.mismatches:
            raise RuntimeError(f"cross-node mismatch: "
                               f"{report.mismatches[:4]}")
        late = {k: h for k, h in report.heights.items()
                if h < sz.net_height}
        if late or len(report.heights) != sz.net_vals + 1:
            raise RuntimeError(f"nodes short of height "
                               f"{sz.net_height}: {report.heights}")
        if report.load_accepted <= 0:
            raise RuntimeError("no load transaction was accepted")
        if len(acked) != sz.writes:
            raise RuntimeError("not every write was read back")
        print(f"[net-4] {sz.net_vals} validators + 1 late full node at "
              f"heights {sorted(report.heights.values())}, no "
              f"block-hash/app-hash mismatch, load accepted "
              f"{report.load_accepted}/{report.load_sent}; "
              f"{len(acked)} acknowledged {TX_SIZE}-byte writes read "
              f"back from another node", flush=True)

    def stage_verdict(self) -> None:
        """Did the device do the work?  Read it off what ran, not off
        a label chosen before the dispatch."""
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs.breaker import CLOSED

        want_platform = "cpu" if self.rehearsal else "tpu"
        count = self.device["count"]
        batch = {"tpu": 0, "cpu": 0, "fallback": 0}
        kernels: dict[str, int] = {}
        platforms: dict[str, int] = {}
        unsharded = []
        for stage, spans in self.spans.items():
            for ev in spans:
                a = ev.get("attrs") or {}
                if ev["name"] == "batch_verify" and \
                        a.get("backend") in ("tpu", "cpu"):
                    batch[a["backend"]] += 1
                    batch["fallback"] += bool(a.get("fallback"))
                elif ev["name"] == "kernel_execute":
                    k, p = str(a.get("kernel")), str(a.get("platform"))
                    kernels[k] = kernels.get(k, 0) + 1
                    platforms[p] = platforms.get(p, 0) + 1
                    if a.get("bucket", 0) >= 1024 and \
                            a.get("devices") != count:
                        unsharded.append((stage, a.get("bucket"),
                                          a.get("devices")))
        for stage in ("seam-175", "seam-10k", "light-1k", "net-4"):
            if not any(ev["name"] == "batch_verify"
                       for ev in self.spans.get(stage, ())):
                raise RuntimeError(
                    f"stage {stage} left no batch_verify span")
        breaker = crypto_batch.tpu_breaker().state
        cpu_observed = [
            ln for ln in libmetrics.DEFAULT.render().splitlines()
            if "batch_verify_seconds_count" in ln
            and 'backend="cpu"' in ln and not ln.endswith(" 0")]
        self.verdict = {
            "batch_verify_spans": batch, "kernel_execute": kernels,
            "output_platform": platforms, "breaker": breaker,
            "devices": count,
            "unsharded_1024_lane_dispatches": len(unsharded)}
        print(f"[verdict] {json.dumps(self.verdict)}", flush=True)
        if batch["tpu"] == 0 or batch["cpu"] or batch["fallback"]:
            raise RuntimeError(f"batch_verify spans: {batch}")
        if cpu_observed:
            raise RuntimeError(
                f"ed25519 batches were observed on the CPU verifier: "
                f"{cpu_observed}")
        if breaker != CLOSED:
            raise RuntimeError(f"TPU breaker is {breaker}")
        if set(kernels) != {"pallas"}:
            raise RuntimeError(f"kernels run: {kernels}")
        if set(platforms) != {want_platform}:
            raise RuntimeError(
                f"masks came off {platforms}, not {want_platform}")
        if count > 1 and unsharded:
            raise RuntimeError(
                f"dispatches of >= 1024 lanes not on all {count} "
                f"devices: {unsharded[:4]}")

    # -- summary -------------------------------------------------------
    def summary(self) -> dict:
        out = {"rehearsal": REHEARSAL_BANNER} if self.rehearsal else {}
        out.update({
            "seed": self.seed, "versions": self.versions,
            "stages": self.stages, "verdict": self.verdict,
            "claim": None})
        return out

    def result(self) -> dict:
        """The last stdout line: exactly ``ok`` and ``device``."""
        return {"ok": not self.rehearsal, "device": self.device}


def _flip_bit(rng: random.Random, b: bytes, lo: int, hi: int) -> bytes:
    """b with one seeded bit flipped in bytes [lo, hi)."""
    i = rng.randrange(lo, hi)
    return b[:i] + bytes([b[i] ^ (1 << rng.randrange(8))]) + b[i + 1:]


def _edge_lanes(rng: random.Random, items: list) -> list:
    """Forged lanes and the ZIP-215 edge vectors of
    tests/test_ops_ed25519.py, seeded: (pub, msg, sig) triples whose
    verdicts only the golden model is trusted to know."""
    from cometbft_tpu.crypto import _ed25519_ref as ref

    def small_order() -> bytes:
        while True:
            pt = ref.decompress(rng.randbytes(32))
            if pt is None:
                continue
            tor = ref.scalar_mult(ref.L, pt)
            if tor != (0, 1):
                return ref.compress(tor)

    def honest():
        return items[rng.randrange(len(items))]

    lanes = []
    for _ in range(3):                      # forged R, forged S
        pub, msg, sig = honest()
        lanes.append((pub, msg, _flip_bit(rng, sig, 0, 32)))
        pub, msg, sig = honest()
        lanes.append((pub, msg, _flip_bit(rng, sig, 32, 63)))
    pub, msg, sig = honest()
    lanes.append((pub, msg + b"tampered", sig))         # wrong message
    pub, msg, sig = honest()
    lanes.append((pub, msg, sig[:32] + bytes(32)))      # S = 0
    pub, msg, sig = honest()                            # S + L
    s = int.from_bytes(sig[32:], "little") + ref.L
    lanes.append((pub, msg, sig[:32] + s.to_bytes(32, "little")))
    lanes.append((rng.randbytes(32), msg, sig))         # arbitrary A
    pub, msg, sig = honest()
    lanes.append((pub, msg, sig[:32] + rng.randbytes(32)))
    # small-order A and R with S = 0: accepted cofactored, any message
    for msg in (b"", b"arbitrary", rng.randbytes(100)):
        lanes.append((small_order(), msg, small_order() + bytes(32)))
    # non-canonical y: p + 1 encodes the identity (y = 1)
    enc = (ref.P + 1).to_bytes(32, "little")
    lanes.append((small_order(), b"m", enc + bytes(32)))
    lanes.append((enc, b"m", small_order() + bytes(32)))
    return lanes


def _rehearse_on_cpu() -> None:
    """Route the device path to the Pallas kernel in interpret mode at
    a block of 8 lanes and one 16-lane bucket, on the CPU — so this
    script's own logic can be debugged without a chip.  The verdict
    still requires the pallas kernel and a closed breaker; it cannot
    print ok: true."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import ed25519_jax as ej
    from cometbft_tpu.ops import ed25519_pallas as ep

    os.environ["COMETBFT_TPU_KERNEL"] = "pallas"   # auto: xla on cpu
    ep.BLOCK = 8
    ej._BUCKETS[:] = [16]       # also the pipeline tile: 40 > 16 tiles
    launch = ej._launch

    def interpreted(*args, **kw):
        kw["interpret"] = True
        return launch(*args, **kw)

    ej._launch = interpreted
    crypto_batch._backend = "tpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="all validator sets, commits, forgeries and "
                         "transactions derive from it")
    ap.add_argument("--rehearsal", action="store_true",
                    help="debug this script on a CPU at tiny sizes "
                         "(interpret mode); never a chip result")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    sizes = TINY if args.rehearsal else REAL
    smoke = Smoke(args.seed, sizes, args.rehearsal)
    if args.rehearsal:
        print(REHEARSAL_BANNER, flush=True)
    t0 = time.perf_counter()
    smoke.run("device", smoke.stage_device)
    if args.rehearsal:
        _rehearse_on_cpu()
    smoke.run("native", smoke.stage_native)
    smoke.run("seam-175", lambda: smoke.stage_seam(sizes.qa_vals))
    smoke.run("seam-10k", lambda: smoke.stage_seam(sizes.star_vals))
    smoke.run("light-1k", smoke.stage_light)
    smoke.run("net-4", smoke.stage_net)
    smoke.run("verdict", smoke.stage_verdict)
    print(f"[smoke] all stages passed in "
          f"{time.perf_counter() - t0:.1f} s wall (set-up facts, not "
          f"results)", flush=True)
    print(json.dumps(smoke.summary(), ensure_ascii=False), flush=True)
    print(json.dumps(smoke.result(), ensure_ascii=False), flush=True)
    return REHEARSAL_EXIT if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
