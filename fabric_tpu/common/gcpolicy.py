"""The process's cyclic-collector policy: no full walk of the heap on
the validate-and-commit path.

A block's objects die young and by reference count: a transaction's
work item, its pending policy evaluation and its rwset footprint are
dropped when the block's flags are out.  CPython's defaults are sized
for a script, not for that.  Generation 0 is collected every 700 net
allocations of tracked objects, some forty times a block, so whatever
a block holds in hand is promoted to generation 2 within milliseconds,
and once a quarter of the old heap's count has arrived there a full
collection stops every thread to walk all of it: the interpreter, JAX,
the protobufs, the program, 0.2 s and more each, dozens of times a
minute (PERF.md, Findings of PR 23 and PR 26).  Three parts:

- :func:`settle`, once the process is warm (the first ``Committer``
  built, and ``peer node start``): ``gc.collect()`` then
  ``gc.freeze()`` move the heap that start-up leaves behind where no
  later collection walks it.  It is never garbage (modules, classes,
  the channel's configuration), so nothing is lost by not looking.
  :func:`absorb` does the same again after the provider's first (cold)
  enqueue of a kernel shape, whose trace-and-lower leaves a heap that
  lives as long as the process.
- ``gc.set_threshold``: generation 0 sized to the pipeline, not to the
  statement (the constant below), so that no threshold fires while
  blocks are in hand.
- :func:`pipeline_empty`, where the pipeline has just run empty (a lone
  block committed, a stream's last flags out): the young generations
  are collected THEN, when nearly everything in them is already dead
  and the walk is a few hundred objects, not when a count is crossed
  mid-block with tens of thousands alive.  Every tenth of these is a
  full collection, CPython's own ratio: with the start-up heap frozen
  it walks only what has been built since, and it is what still
  reclaims a cycle that grew old.

Collection stays enabled throughout.  A collector policy set from a
library is a debt, named here: it is process-wide, it is set in this
one module, it is idempotent, there is no option, and an embedding
process that wants its own sets it after building its first
``Committer``.  The engagement shows on /metrics
(``process_gc_frozen_objects``, ``process_gc_collections_total`` by
generation, ``process_gc_pause_seconds_total``) and on a trace:
tracelens records a ``gc.pause`` span for every collection of
generation 1 or older.
"""

from __future__ import annotations

import gc
import threading

# Tracked objects alive while blocks are in hand (sandbox CPU, counts:
# gc.get_count()[0] sampled under a threshold nothing reaches; PR 26):
# ~32 a transaction, so 11-16 thousand for a lone 500-tx block, 33
# thousand for a lone 1000-tx 3-of-5 block, 47 and 100 thousand with
# store_stream's pipeline full of 500-tx and 1000-tx blocks.  200,000
# is twice the largest, so a threshold fires only on code that never
# reaches pipeline_empty(); on the chip (PR 26, majority5-1000tx.catchup,
# 45 s, one seed a reading): 8,000 read 7,859 committed tx/s, 200,000
# with pipeline_empty() 8,554, the parent 5,849.
GEN0_THRESHOLD = 200_000
GEN1_THRESHOLD = 10   # CPython's own
GEN2_THRESHOLD = 10   # CPython's own; also pipeline_empty()'s ratio

_lock = threading.Lock()
_settled = False


def settle() -> bool:
    """Freeze the start-up heap and size generation 0, once a process:
    True for the call that did it, False for every later one."""
    global _settled
    with _lock:
        if _settled:
            return False
        _settled = True
        gc.collect()
        gc.freeze()
        gc.set_threshold(GEN0_THRESHOLD, GEN1_THRESHOLD, GEN2_THRESHOLD)
        return True


def absorb() -> None:
    """Freeze what was built since (a kernel shape's trace-and-lower),
    in a process that has settled; nothing in one that has not."""
    with _lock:
        if _settled:
            gc.collect()
            gc.freeze()


def pipeline_empty() -> None:
    """Collect the young generations now that no block is in hand; a
    full collection every GEN2_THRESHOLD-th time.  Nothing in a process
    that has not settled."""
    if _settled:
        gc.collect(2 if gc.get_count()[2] >= GEN2_THRESHOLD else 1)


__all__ = ["GEN0_THRESHOLD", "absorb", "pipeline_empty", "settle"]
