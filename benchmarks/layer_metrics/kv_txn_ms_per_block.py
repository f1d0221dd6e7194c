"""Committer and ledger (`ledger/kvledger.py` `_flush_group`): the wall
of the window's `kv_txn` spans over blocks: a commit group's ONE KV
transaction (state, history, block index and savepoints of every block
the group holds), which on the sqlite store is one write transaction
against the state's b-tree.  `commit_ms_per_block` sums it with the
other stages; alone it is what a state past the store's page cache
costs the committer.  A program whose `kv_txn` spans lack `rows` gives
nothing to read.

`# kv_txn` prints beside it, a group: the groups, their blocks, the
rows (puts and deletes) their transactions wrote, their wall and their
thread CPU; the wall less the CPU is the wait for the interpreter's
lock and for the disk."""

from benchlib import cpuspans, spans


def read(obs):
    groups = [e for e in spans.named(obs, "kv_txn") if "rows" in e["args"]]
    n = obs.get("blocks")
    if not groups or not n:
        return None
    g = len(groups)
    timed = cpuspans.timed(groups)
    spans.say("kv_txn", {
        "groups": g,
        "blocks_per_group": sum(e["args"]["blocks"] for e in groups) / g,
        "rows_per_group": sum(e["args"]["rows"] for e in groups) / g,
        "wall_ms_per_group": spans.total_ms(groups) / g,
        "cpu_ms_per_group": sum(e["tdur"] for e in timed) / 1e3 / g if timed else None,
    })
    return spans.total_ms(groups) / n
