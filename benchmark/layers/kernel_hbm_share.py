"""The kernel's share of the HBM roofline: bytes the calls must move
(lib/kernel_cost.py, from their shapes) over the kernel's device time,
over the published HBM peak of the device kind (lib/peaks.json).  The
kernel is compute-bound on int32 VPU work for which no peak is
published, so this reads low by design; it says how far from the
memory roof the kernel sits, not how good it is."""
from benchmark.layers.kernel_device_us_per_lane import (
    kernel_time_and_lanes,
)
from benchmark.lib import kernel_cost


def read(obs):
    got = kernel_time_and_lanes(obs)
    if got is None:
        return None
    seconds, lanes = got
    peak = kernel_cost.peaks(obs.device_kind)["hbm_bytes_per_s"]
    return 100.0 * kernel_cost.hbm_bytes(lanes) / seconds / peak
