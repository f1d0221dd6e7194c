"""When a timing of `mixedcc-8cc-5org-1000tx` is a timing of the regime
the cell is for: blocks in which all eight chaincodes and every planted
class meet, decided by a validator whose mask folds as the deployment
guarantees, with every lane on the device.  Two numbers hold the TRAFFIC
to the regime, from what the world kept of its own blocks
(`worlds/x509-mixedcc.py`), and two the PROGRAM, from its own counts:

    planted_classes_missing_from_a_block
        over the blocks the window yielded, the classes a block has
        room for (`due_classes`) of which it holds no transaction
        (`planted_classes`)
    chaincodes_missing_from_a_block
        over the same blocks, the chaincodes a block owes
        (`due_chaincodes`: those of which it would hold 8 transactions
        or more by their popularity) that none of its transactions
        writes (`chaincodes_drawn`)
    tolerated_bad_lanes_differing_from_planted
        per yielded block, the endorsement lanes the mask refused in
        transactions the program left VALID
        (`peer.txvalidator.tolerated_tally()["recent_blocks"]`, from
        process start, the window's being the last `len(yielded)`)
        against those the world planted in that block
        (`tolerated_lanes`), where they differ: a program that folds the
        mask otherwise than "a failed signature invalidates a
        transaction only where the policy is unmet without it" counts
        another number, whatever its flags
    lanes_sealed_by_the_host
        lanes of the window whose mask the host sealed (a race past a
        stalled flush, a batch under `min_device_batch`, the
        `host_fraction` tail; `lanes_window`): the timing would be of
        another path.  Failover and the breaker are the engine's own

each with limit 0.  A chaincode is owed by its share of `block_txs`, so
a test's small blocks are held to the same rule (and owe none).  No
depth, bucket or cache size of the program's is named here: a later
change to them is free."""

HOST_SEALERS = ("host_race", "small", "host_fraction")


def numbers(cell) -> dict:
    from fabric_tpu.peer.txvalidator import tolerated_tally

    world = cell.world
    seen = sorted({bno for bno, _flags in cell.yielded})
    classes = sum(1 for b in seen for c in world.due_classes[b]
                  if not world.planted_classes[b].get(c))
    chaincodes = sum(1 for b in seen for ns in world.due_chaincodes[b]
                     if not world.chaincodes_drawn[b].get(ns))
    recent = tolerated_tally()["recent_blocks"]
    took = len(cell.yielded)
    window = recent[len(recent) - took:] if took else []
    differ = 0
    for k, (bno, _flags) in enumerate(cell.yielded):
        number, counted = window[k] if k < len(window) else (None, 0)
        planted = world.tolerated_lanes[bno]
        differ += abs(planted - counted) if number == 1 + bno else max(1, planted)
    return {
        "planted_classes_missing_from_a_block": (classes, 0),
        "chaincodes_missing_from_a_block": (chaincodes, 0),
        "tolerated_bad_lanes_differing_from_planted": (differ, 0),
        "lanes_sealed_by_the_host": (
            sum(cell.lanes_window.get(k, 0) for k in HOST_SEALERS), 0),
    }
