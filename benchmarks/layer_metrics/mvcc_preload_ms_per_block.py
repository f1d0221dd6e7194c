"""Committer and ledger (`ledger/txmgmt.py` `MVCCValidator._preload`):
the wall of the window's `mvcc.preload` spans over blocks: the ONE bulk
read (`VersionedDB.get_state_many`) that fetches the committed version
of every key a block's transactions read, before any of them is
checked.  On a ledger whose rows a pass wrote seconds ago it is a few
lookups in the commit group's overlay; on a populated one it is the
store's own reads, past sqlite's page cache where the state is larger.
A program without the span gives nothing to read.

`# mvcc_preload` prints beside it, a block: the distinct keys asked and
the rows found (the spans' own counts) and the spans' thread CPU; the wall less that is the wait for the interpreter's lock and for
the disk."""

from benchlib import cpuspans, spans


def read(obs):
    preloads = spans.named(obs, "mvcc.preload")
    n = obs.get("blocks")
    if not preloads or not n:
        return None
    counted = [e["args"] for e in preloads if "keys_asked" in e["args"]]
    spans.say("mvcc_preload", {
        "blocks": n,
        "keys_asked_per_block": sum(a["keys_asked"] for a in counted) / n,
        "rows_found_per_block": sum(a["rows_found"] for a in counted) / n,
        "cpu_ms_per_block": cpuspans.cpu_ms_per_block(obs, "mvcc.preload"),
    })
    return spans.total_ms(preloads) / n
