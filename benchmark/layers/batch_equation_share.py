"""Share of the window's single-device Pallas dispatches that one batch
equation settled: 100 × Δheld / (Δheld + Δfailed + Δbypassed) of
`cometbft_crypto_batch_equation_total{outcome}` (ops/ed25519_jax, counted
where a dispatch is settled).  None where the program has no such
counter or dispatched nothing in the window."""
from benchmark.lib import probes

FAMILY = "cometbft_crypto_batch_equation_total"


def read(obs):
    found = probes.select(obs.metrics, FAMILY)
    settled = sum(found.values())
    if settled <= 0:
        return None
    return 100.0 * probes.total(obs.metrics, FAMILY, outcome="held") \
        / settled
