"""Harness: the median over the window's passes of (transactions of
the pass) / (wall of the pass): the rate of a typical pass.  It sheds
the stalls that the end-to-end `committed_tx_per_s` (all transactions
over all timed wall) carries, so the two read together say whether a
change moved the typical pass or the stalls."""

from benchlib import stats


def read(obs):
    if not obs["pass_walls_s"]:
        return None
    return stats.median_rate(obs["pass_txs"], obs["pass_walls_s"])
