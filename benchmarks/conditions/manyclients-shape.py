"""When a timing of `manyclients-10k` is a timing of the regime the
cell is for: blocks whose creators outnumber what a peer remembers
between them.  The seed draws the creators, so the seed could draw a
run too uniform to be that; this holds the TRAFFIC, not the program, to
the regime, from what the world kept of its own blocks
(`worlds/x509-manyclients.py`):

    blocks_with_too_few_distinct_creators        blocks with fewer than 400
                                                 distinct creators in 1,000
                                                 transactions
    two_block_runs_with_too_few_distinct_creators  neighbouring blocks (one flush
                                                 in catch-up) with fewer than 700
                                                 between them

each with limit 0.  The floors are shares of `block_txs` (0.4 and 0.7),
so a test's tiny blocks are held to the same regime.  No size of any
table or cache is named here: a later change to them is free.  That no
flush changed kernel inside the window is the engine's own to catch
(no compile and no new bucket in the window)."""

A_BLOCK = 0.4
TWO_BLOCKS = 0.7


def numbers(cell) -> dict:
    world = cell.world
    n = int(cell.deployment["block_txs"])
    return {
        "blocks_with_too_few_distinct_creators": (
            sum(1 for c in world.creators_per_block if c < A_BLOCK * n), 0),
        "two_block_runs_with_too_few_distinct_creators": (
            sum(1 for c in world.creators_per_two_blocks if c < TWO_BLOCKS * n), 0),
    }
