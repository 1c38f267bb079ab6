"""Idle time on the device between two tiles of one commit: between
consecutive kernel events of the trace that lie closer than 100 ms
(tiles of one commit; commits are 500 ms apart), the stretch from one's
end to the next's start less whatever other operation ran in it,
median.  Both ends are on the device's clock, so the offset between
the profile's host and device events does not touch it."""
from benchmark.lib import profile, stats

SAME_COMMIT_NS = 100e6


def read(obs):
    if obs.trace is None:
        return None
    events = sorted(profile.kernel_events(obs.trace),
                    key=lambda e: e[1])
    kernels = {tuple(e) for e in events}
    others = [(s, s + d) for dev in obs.trace.get("devices", ())
              for name, s, d in dev["modules"] + dev["ops"]
              if (name, s, d) not in kernels]
    gaps = []
    for (_, s0, d0), (_, s1, _) in zip(events, events[1:]):
        lo, hi = s0 + d0, s1
        if 0 <= hi - lo < SAME_COMMIT_NS:
            busy = profile.union_ns(
                (max(lo, s), min(hi, e)) for s, e in others)
            gaps.append((hi - lo - busy) / 1e3)
    return stats.median(gaps)
