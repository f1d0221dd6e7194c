"""Committer and ledger (`ledger/txmgmt.py` `MVCCValidator._preload`):
of the distinct keys the window's bulk preloads asked the state for
(`keys_asked` on its `mvcc.preload` spans), those that are rows
(`rows_found`), %: the reading that the reads met a state that is
there.  100 on a ledger that holds what its transactions read, near 0
on an unpopulated one, where every read is of a key no block has
written yet.  A program without the span, or a window that asked for
nothing, gives nothing to read."""

from benchlib import spans


def read(obs):
    counted = [e["args"] for e in spans.named(obs, "mvcc.preload") if "keys_asked" in e["args"]]
    asked = sum(a["keys_asked"] for a in counted)
    if not asked:
        return None
    return 100.0 * sum(a["rows_found"] for a in counted) / asked
