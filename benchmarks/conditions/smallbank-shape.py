"""When a timing of `smallbank-100k-zipf` is a timing of the regime the
cell is for: blocks of which MVCC throws a large share away, read
against a state that is really there, with the pipeline intact.  Two
numbers hold the TRAFFIC to the regime, from what the world kept of its
own blocks (`worlds/x509-smallbank.py`), and two the PROGRAM, from its
own counts (from process start, warm-up included):

    blocks_with_too_few_conflicts
        yielded blocks of which the world's own MVCC model invalidated
        under the configuration's `conflict_floor_share` of
        `block_txs`: the draws did not meet, so most of what the device
        verified was kept
    planted_classes_missing_from_a_block
        measured blocks that hold no transaction of a class the
        configuration plants (`planted_classes`)
    reads_that_found_no_row
        per yielded block, the distinct rows the world says the
        block's transactions read on their way into MVCC (`read_keys`:
        every one was populated by the set-up blocks) less the rows
        the program's bulk preload found for that block
        (`ledger.txmgmt.mvcc_tally()["recent_blocks"]`, the window's
        being the last `len(yielded)`), where that is positive: a read
        that was not asked of the state, or was answered "absent" by a
        ledger that does not hold what the deployment says it holds
    most_flushes_held_one_block_alone
        1 where more than half the provider's flushes took in one
        block's lanes alone (`csp.flush_tally()`): the pipeline was
        serialised to depth 1, and the timing is of another path

each with limit 0.  The floor is a share of `block_txs`, the
configuration's own, so a test's small blocks are held to the same.  No depth, bucket or cache size of
the program's is named here: a later change to them is free."""

LONE_FLUSHES_AT_MOST = 1 / 2


def numbers(cell) -> dict:
    from fabric_tpu.ledger.txmgmt import MVCC_COUNTS, mvcc_tally

    world = cell.world
    floor = float(cell.deployment["conflict_floor_share"]) * int(cell.deployment["block_txs"])
    calm = sum(1 for bno, _flags in cell.yielded if world.mvcc_refused[bno] < floor)
    missing = sum(1 for held in world.planted_classes
                  for planted in held.values() if not planted)
    recent = mvcc_tally()["recent_blocks"]
    took = len(cell.yielded)
    window = recent[len(recent) - took:] if took else []
    first = 1 + len(world.setup_blocks)
    found_at = 1 + MVCC_COUNTS.index("rows_found")
    unread = 0
    for k, (bno, _flags) in enumerate(cell.yielded):
        counted = window[k] if k < len(window) else (None,)
        found = counted[found_at] if counted[0] == first + bno else 0
        unread += max(0, world.read_keys[bno] - found)
    flushes = cell.csp.flush_tally()
    lone = flushes["lone"] > LONE_FLUSHES_AT_MOST * flushes["flushes"]
    return {
        "blocks_with_too_few_conflicts": (calm, 0),
        "planted_classes_missing_from_a_block": (missing, 0),
        "reads_that_found_no_row": (unread, 0),
        "most_flushes_held_one_block_alone": (1 if lone else 0, 0),
    }
