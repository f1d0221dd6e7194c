"""Node daemons: the peer, the orderer and the single-process dev node."""


def quiesce(csp) -> None:
    """Join what a node's commit path leaves running before the process
    may exit: the CSP's flush waiters (`TPUCSP.close()`; providers
    without a `close` have none) and the shared host work pool.  A
    thread still inside the device runtime, or a pool task, at
    interpreter exit is the rc=134 "FATAL: exception not rethrown"
    teardown abort.  Call it only once nothing can submit new work."""
    close = getattr(csp, "close", None)
    if close is not None:
        close()
    from fabric_tpu.common import workpool

    workpool.shutdown()
