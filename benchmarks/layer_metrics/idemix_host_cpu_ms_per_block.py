"""Idemix provider (`csp/idemix_provider.py`): thread CPU of the host
half of a flush per block (`tdur`, PR 37), over the spans
`idemix_host_ms_per_block` sums.  They run on the flushes' worker
threads beside the main thread's `collect` and the committer, all on
one interpreter lock: the difference to that wall is their wait for
it.  `# idemix_cpu_shares` prints the four."""

from benchlib import cpuspans, spans

PARTS = ("idemix.prepare", "idemix.normalize", "idemix.rehash", "idemix.pairing")


def read(obs):
    per = {name: cpuspans.cpu_ms_per_block(obs, name) for name in PARTS}
    per = {name: v for name, v in per.items() if v is not None}
    if not per:
        return None
    spans.say("idemix_cpu_shares", per)
    return sum(per.values())
