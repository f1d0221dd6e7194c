"""When a timing of `keylevel-5org-1000tx` is a timing of the regime
the cell is for: a pipelined validator deciding keys whose parameters
the blocks just before it are still writing, with its pipeline intact.
Two numbers hold the TRAFFIC to the regime, from what the world kept of
its own blocks (`worlds/x509-keylevel.py`), and two the PROGRAM, from
its own counts (from process start, warm-up included: the right span
for a thing that may never happen):

    work_blocks_with_too_few_dependent_transactions
        work blocks after the first in which fewer than a twentieth of
        the transactions write an asset whose parameter one of the two
        blocks before wrote (`dependent`): the seed drew neighbours that
        do not meet, so nothing had to wait.  (The first work block
        follows the create blocks, whose assets are the coldest.)
    planted_classes_missing_from_a_block
        classes the block could hold (`due_classes`) of which it holds
        no transaction (`planted_classes`)
    dependent_transactions_not_deferred
        per yielded block, the world's `dependent` less what the
        program deferred of that block
        (`peer.txvalidator.keylevel_tally()["recent_blocks"]`, the
        window's being the last `len(yielded)`), where that is
        positive: a transaction that depended on a block still in
        flight and was decided without waiting for it.  At
        `store_stream`'s default depth the two blocks before are always
        in flight when a block is collected, so the program defers at
        least `dependent` (how many more is timing: the blocks whose
        commits have not landed yet)
    most_flushes_held_one_block_alone
        1 where more than half the provider's flushes took in one
        block's lanes alone (`csp.flush_tally()`: what
        csp_tpu_flush_segments_total over csp_tpu_dispatches_total
        reads on /metrics): the pipeline was serialised to depth 1, and
        the timing is of another path

each with limit 0.  The floor is a share of `block_txs`, so a test's
tiny blocks are held to the same.  No depth, bucket or cache size of
the program's is named here: a later change to them is free."""

DEPENDENT_AT_LEAST = 1 / 20
LONE_FLUSHES_AT_MOST = 1 / 2


def numbers(cell) -> dict:
    from fabric_tpu.peer.txvalidator import keylevel_tally

    world = cell.world
    n = int(cell.deployment["block_txs"])
    work = [b for b, kind in enumerate(world.block_kinds) if kind == "work"]
    starved = sum(1 for b in work[1:] if world.dependent[b] < DEPENDENT_AT_LEAST * n)
    missing = sum(
        1 for due, held in zip(world.due_classes, world.planted_classes)
        for c in due if not held.get(c)
    )
    recent = keylevel_tally()["recent_blocks"]
    took = len(cell.yielded)
    window = recent[len(recent) - took:] if took else []
    short = 0
    for k, (bno, _flags) in enumerate(cell.yielded):
        number, deferred = window[k] if k < len(window) else (1 + bno, 0)
        short += max(0, world.dependent[bno] - (deferred if number == 1 + bno else 0))
    flushes = cell.csp.flush_tally()
    lone = flushes["lone"] > LONE_FLUSHES_AT_MOST * flushes["flushes"]
    return {
        "work_blocks_with_too_few_dependent_transactions": (starved, 0),
        "planted_classes_missing_from_a_block": (missing, 0),
        "dependent_transactions_not_deferred": (short, 0),
        "most_flushes_held_one_block_alone": (1 if lone else 0, 0),
    }
