"""Device time of the verification kernel per padded lane: the device
durations of the kernel's events in the profiler trace, over the
padded lanes of the dispatches in the traced sub-window."""
from benchmark.lib import probes, profile


def kernel_time_and_lanes(obs):
    """(seconds of kernel events, padded lanes dispatched) in the
    traced sub-window, or None."""
    if obs.trace is None:
        return None
    events = profile.kernel_events(obs.trace)
    lanes = sum(probes.attr(ev, "bucket", 0) for ev in obs.trace_spans
                if ev["name"] == "kernel_execute")
    if not events or not lanes:
        return None
    return sum(e[2] for e in events) / 1e9, lanes


def read(obs):
    got = kernel_time_and_lanes(obs)
    if got is None:
        return None
    seconds, lanes = got
    return seconds * 1e6 / lanes
