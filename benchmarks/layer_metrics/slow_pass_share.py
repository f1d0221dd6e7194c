"""Harness: share of the window's passes whose wall is over 1.25 times
the run's median pass wall: how often a stall comes.  The end-to-end
rate carries the stalls' cost; this counts them."""

from benchlib import stats


def read(obs):
    return stats.slow_share(obs["pass_walls_s"]) if obs["pass_walls_s"] else None
