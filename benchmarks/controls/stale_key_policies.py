"""A pipelined validator for which no key is ever pending: every
transaction is decided while its block is collected, under the
parameters committed at that moment, whatever the blocks still in
flight are about to write (the program before PR 40).  Breaks "every
written key is decided under the VALIDATION_PARAMETER committed by the
block before": an asset created or transferred in one of the blocks in
flight is decided under its older parameter (or none), so its new
owner's sound transactions are refused and a previous owner's late
transaction is accepted: the flags differ, and a write the channel's
rules forbid lands in the state."""


def apply():
    from fabric_tpu.peer.txvalidator import _KeyWindow

    _KeyWindow.pending = lambda self: None
