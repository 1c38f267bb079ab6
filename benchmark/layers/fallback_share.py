"""Share of ed25519 batches that did not run on the device: spans with
backend != tpu or the fallback flag.  Must read 0."""
from benchmark.lib import probes


def read(obs):
    batches = [ev for ev in obs.spans if ev["name"] == "batch_verify"
               and probes.attr(ev, "backend") in ("tpu", "cpu")]
    if not batches:
        return None
    off = sum(1 for ev in batches
              if probes.attr(ev, "backend") != "tpu"
              or probes.attr(ev, "fallback"))
    return 100.0 * off / len(batches)
