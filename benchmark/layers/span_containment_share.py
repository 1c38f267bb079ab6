"""Kernel device events of the traced window that lie wholly inside a
`kernel_execute` span placed on the profiler's clock, over all of
them: the check of the clock the spans and the device trace share
(must read 99 or more; a program whose tiled `kernel_execute` does
not run from dispatch to mask reads less)."""
from benchmark.lib import spantree


def read(obs):
    return spantree.containment_share(obs.trace, obs.spans)
