"""Median over the traced dispatches of (the start of the `launch`
span -> the end of the `device_wait` span) less the time the kernel
ran on the device: what one dispatch costs around the kernel between
the jitted call and the host's wake-up.  Both ends are on the host's
clock; the device trace gives only the kernel's duration.  In a tiled
dispatch a later tile's reading includes its wait for the tile
before."""
from benchmark.lib import spantree, stats


def read(obs):
    return stats.median(spantree.dispatch_overheads_us(
        obs.trace, obs.spans))
