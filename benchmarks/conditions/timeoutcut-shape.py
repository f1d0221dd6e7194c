"""When a timing of `timeoutcut-2s` is a timing of the regime the cell
is for: a backlog of blocks of very uneven size, as `BatchTimeout` cuts
them from a light, bursty load.  The configuration's `arrival_seed`
draws the arrival times, and a later edit of the draw, the cycle or the
pass's length could make a pass too even to be that; this holds the
TRAFFIC, not the program, to the regime, from what the world kept of
its own cut
(`worlds/x509-timeoutcut.py`: `txs_per_block`, `cut_by`).  With n the
blocks of the pass:

    full_blocks_over_a_quarter_of_the_pass
        blocks cut by the count or the byte rule (full blocks), as far
        as they outnumber n / 4: the full-block regime is
        `majority5-1000tx.catchup`'s
    small_blocks_short_of_an_eighth_of_the_pass
        blocks under 8 transactions, as far as they fall short of n / 8
    distinct_block_sizes_short_of_five_sixteenths_of_the_pass
        distinct sizes among the blocks, as far as they fall short of
        5 n / 16

each a count of what is missing or too many, with limit 0.  At the
cell's size (64 blocks) the floors are 16 full at most, 8 small and 20
distinct sizes at least; the configuration's draw gives 5, 21 and 31,
other draws (arrival seeds 3400000001-40) 3-5, 16-23 and 30-40.
They are shares of the pass, so a test's tiny world is held to the
same.  No size of the program's is named here (no bucket, no
`min_device_batch`, no depth): a later change to them is free."""

import math

SMALL_TXS = 8
FULL_AT_MOST, SMALL_AT_LEAST, DISTINCT_AT_LEAST = 1 / 4, 1 / 8, 5 / 16


def numbers(cell) -> dict:
    sizes = cell.world.txs_per_block
    n = len(sizes)
    full = sum(1 for by in cell.world.cut_by if by != "timeout")
    small = sum(1 for s in sizes if s < SMALL_TXS)
    return {
        "full_blocks_over_a_quarter_of_the_pass": (
            max(0, full - math.floor(FULL_AT_MOST * n)), 0),
        "small_blocks_short_of_an_eighth_of_the_pass": (
            max(0, math.ceil(SMALL_AT_LEAST * n) - small), 0),
        "distinct_block_sizes_short_of_five_sixteenths_of_the_pass": (
            max(0, math.ceil(DISTINCT_AT_LEAST * n) - len(set(sizes))), 0),
    }
