"""Harness: the 90th percentile (nearest rank) of block latency from
the due time.  Per-layer in both steady cells: the tail of a steady
cell is the length of a generation-2 pause, and the 90th lies at the
edge of the blocks a pause reaches, so it hops between the two sides
from run to run (PERF.md, Noise).

As `block_commit_p90_ms.steady` it stands beside the end-to-end 95th
of `solo1-500tx.steady`.  As `block_commit_p90_ms.unguarded` it is the
record of `majority5-1000tx.steady`'s tail, which no end-to-end metric
guards yet: `moves` has to name a metric the cell reports, so it names
the median, which a stall moves only through the blocks queued behind
it."""

from benchlib import stats


def read(obs):
    if not obs["latencies_s"]:
        return None
    return stats.percentile([1e3 * x for x in obs["latencies_s"]], 90)
