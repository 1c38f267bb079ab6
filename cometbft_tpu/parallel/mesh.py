"""Device-mesh sharding for the signature-verification / vote-tally offload.

Reference parallelism mapped (SURVEY §2.11): the reference's batch verifier
(crypto/ed25519/ed25519.go:189-222) is single-host; here very large batches
(>= 10k signatures, BASELINE config #5) shard across a TPU mesh — lanes are
data-parallel, and the vote-power tally reduces with an XLA psum over ICI.

Validators are WAN peers, so the mesh lives *inside* one node's TPU pod;
p2p traffic never touches ICI (SURVEY §5 "distributed communication backend").
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..libs import tracing
from ..ops import device
from ..ops.ed25519_jax import _verify_kernel

BATCH_AXIS = "sig_batch"


def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the first n_devices JAX devices."""
    device.probe()      # first touch places the compile cache
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested {n_devices}-device mesh but only "
                f"{len(devs)} JAX devices are available")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (BATCH_AXIS,))


@functools.lru_cache(maxsize=None)
def _sharded_verify_fn(ndev: int, kernel: str, interpret: bool,
                       block: int):
    """Jitted shard_map'ed batch verify over an ndev mesh; per-shard
    body is the selected kernel behind the packed uint8 wire layout
    (a/r [shard,32]u8, s/k [shard,64]u8 — every input shards on the
    lane axis and the int32 unpack runs per-device).  Cached per
    configuration — the jit itself caches per shape."""
    mesh = make_mesh(ndev)
    from ..ops.ed25519_jax import _byte_cols, _win_cols
    if kernel == "pallas":
        from ..ops import ed25519_pallas as ep

        def body(a, r, s, k):
            return ep.verify_cols(
                _byte_cols(a), _byte_cols(r),
                _win_cols(s), _win_cols(k), interpret=interpret,
                block=block or ep.BLOCK)
    else:
        def body(a, r, s, k):
            return _verify_kernel(a, r, _win_cols(s), _win_cols(k))
    # the jitted executable takes the body's name: what a compile log
    # shows for this kernel
    body.__name__ = f"sharded_{kernel}_verify"

    shard = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(BATCH_AXIS), P(BATCH_AXIS),
                  P(BATCH_AXIS), P(BATCH_AXIS)),
        out_specs=P(BATCH_AXIS),
        # the varying-axes check cannot type a pallas_call body (its
        # out_shape and in-kernel constants carry no mesh axes); the
        # body is lane-parallel with no collective for it to guard
        check_vma=kernel != "pallas",
    )
    return jax.jit(shard)


class PipelinePartitioner:
    """Per-pipeline pre-partitioning (SNIPPETS: pjit performs best
    when inputs arrive already partitioned per its in_specs — then
    the call never re-partitions).  The mesh, the NamedSharding and
    the jitted shard_map'ed kernel all resolve ONCE here; each tile
    of the verification pipeline then costs one async sharded
    ``device_put`` per input plus the jit call — multi-chip dispatch
    overhead is paid once per pipeline, not once per tile.

    ``dispatch`` returns the UN-forced device array (JAX async
    dispatch): the pipeline settles it with np.asarray only after the
    next tile is in flight.

    A path of its own: it shards each of the four arrays on the lane
    axis, so it keeps the four-array signature and is fed the views of
    the wire buffer (ops/ed25519_jax.wire_views).  The single-device
    dispatch's one packed transfer (ops/ed25519_jax._launch) shares no
    logic with it."""

    def __init__(self, ndev: int, kernel: str = "xla",
                 interpret: bool = False, block: int = 0):
        from jax.sharding import NamedSharding
        if kernel == "pallas":
            from ..ops import ed25519_pallas as ep
            block = block or ep.BLOCK
        else:
            interpret, block = False, 0     # ignored by the xla body
        self.ndev = ndev
        self.kernel = kernel
        self.block = block
        self.mesh = make_mesh(ndev)
        self.sharding = NamedSharding(self.mesh, P(BATCH_AXIS))
        self.fn = _sharded_verify_fn(ndev, kernel, interpret, block)

    def _padded(self, m: int) -> int:
        shard = -(-m // self.ndev)
        if self.block:
            shard = -(-shard // self.block) * self.block
        return shard * self.ndev

    def dispatch(self, a_b, r_b, s_w8, k_w8):
        m = a_b.shape[0]
        m2 = self._padded(m)
        if m2 != m:
            pad = m2 - m
            a_b = np.concatenate([a_b, np.zeros((pad, 32), a_b.dtype)])
            r_b = np.concatenate([r_b, np.zeros((pad, 32), r_b.dtype)])
            s_w8 = np.concatenate(
                [s_w8, np.zeros((pad, 64), s_w8.dtype)])
            k_w8 = np.concatenate(
                [k_w8, np.zeros((pad, 64), k_w8.dtype)])
        # async sharded transfers into the pre-resolved sharding —
        # the jitted call below sees correctly-partitioned inputs
        with tracing.span(tracing.CRYPTO, "h2d"):
            da = jax.device_put(a_b, self.sharding)
            dr = jax.device_put(r_b, self.sharding)
            ds = jax.device_put(s_w8, self.sharding)
            dk = jax.device_put(k_w8, self.sharding)
        with tracing.span(tracing.CRYPTO, "launch"):
            return self.fn(da, dr, ds, dk)


@functools.lru_cache(maxsize=None)
def pipeline_partitioner(ndev: int, kernel: str = "xla",
                         interpret: bool = False,
                         block: int = 0) -> PipelinePartitioner:
    """Cached partitioner per (ndev, kernel, interpret, block) — the
    once-per-pipeline setup amortizes to once per process."""
    return PipelinePartitioner(ndev, kernel, interpret, block)


def verify_sharded(a_b, r_b, s_w8, k_w8, *, ndev: int,
                   kernel: str = "xla", interpret: bool = False,
                   block: int = 0) -> np.ndarray:
    """Data-parallel batch verify over all ndev devices (SURVEY §2.11:
    pjit/shard_map row).  Pads the lane count so every shard is equal
    (and, for pallas, a block multiple); padding lanes are garbage and
    simply sliced off — the caller masks pre-bad lanes itself.
    Returns the exact per-lane ok mask for the original m lanes."""
    m = a_b.shape[0]
    part = pipeline_partitioner(ndev, kernel, interpret, block)
    ok = np.asarray(part.dispatch(a_b, r_b, s_w8, k_w8))
    return ok[:m]


def sharded_verify_tally(mesh: Mesh):
    """Build the jitted multi-chip step: verify signatures sharded over the
    mesh; the collective is a psum of per-shard valid-lane counts.

    Returns fn(a_bytes[n,32]u8, r_bytes[n,32]u8, s_w8[n,64]u8,
               k_w8[n,64]u8) -> (ok[n] bool, valid_count i32)
    (s_w8/k_w8: lane-major 4-bit windows, ed25519_jax._windows_u8).

    n must be a multiple of the mesh size.  Voting-power totals are
    aggregated on the host from the exact per-lane mask: validator powers
    are int64 (total capped at MaxInt64/8, types/validator_set.go), which
    TPUs don't sum natively — the mask transfer is 1 byte/lane, so the
    host-side exact tally costs nothing at 10k lanes.
    """

    from ..ops.ed25519_jax import _win_cols

    def step(a, r, s, k):
        ok = _verify_kernel(a, r, _win_cols(s), _win_cols(k))
        count = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), BATCH_AXIS)
        return ok, count

    shard = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(BATCH_AXIS), P(BATCH_AXIS),
                  P(BATCH_AXIS), P(BATCH_AXIS)),
        out_specs=(P(BATCH_AXIS), P()),
    )
    return jax.jit(shard)
