"""Idemix provider (`csp/idemix_provider.py`): lanes submitted over
lanes computed, summed over the window's `idemix.enqueue` spans
(`lanes` over `bucket`): what is missing to 100% is BN254 kernel time
spent on padding."""

from benchlib import spans


def read(obs):
    launches = spans.named(obs, "idemix.enqueue")
    computed = sum(e["args"].get("bucket", 0) for e in launches)
    if not computed:
        return None
    return 100.0 * sum(e["args"].get("lanes", 0) for e in launches) / computed
