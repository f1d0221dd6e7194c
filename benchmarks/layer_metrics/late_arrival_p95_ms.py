"""Harness: how late the generator handed blocks in (95th percentile,
nearest rank).  A starved generator must not be read as a fast peer."""

from benchlib import stats


def read(obs):
    if not obs["lateness_s"]:
        return None
    return stats.percentile([1e3 * x for x in obs["lateness_s"]], 95)
