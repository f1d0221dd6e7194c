"""Harness: the 95th percentile (nearest rank) of block latency from
the due time, as `block_commit_p95_ms.unguarded` in
`majority5-1000tx.steady`.  There it is not end-to-end: a window of
run_seconds holds 135 blocks, too few for ten samples beyond it, and
runs of one code spread by a quarter (PERF.md, Noise).  That cell's
tail is recorded here and guarded by nothing.  In `solo1-500tx.steady`
the 95th is the end-to-end tail and the benchmark computes it itself."""

from benchlib import stats


def read(obs):
    if not obs["latencies_s"]:
        return None
    return stats.percentile([1e3 * x for x in obs["latencies_s"]], 95)
