"""Solo consenter: single-node ordering for dev/test.

Reference: orderer/consensus/solo/consensus.go (~200 LoC): a goroutine
draining the submit channel through the blockcutter with a batch timer.
Here: a daemon thread + queue.Queue; same cut triggers (count/bytes from
the cutter, timeout from the timer).
"""

from __future__ import annotations

import queue
import threading
import time

from fabric_tpu.devtools.lockwatch import spawn_thread

from fabric_tpu.orderer.blockcutter import BlockCutter
from fabric_tpu.orderer.blockwriter import BlockWriter
from fabric_tpu.protos.common import common_pb2


class SoloChain:
    def __init__(
        self,
        cutter: BlockCutter,
        writer: BlockWriter,
        batch_timeout_s: float = 2.0,
        on_block=None,
    ):
        self._cutter = cutter
        self._writer = writer
        self._timeout = batch_timeout_s
        self._on_block = on_block or (lambda blk: None)
        self._q: queue.Queue = queue.Queue()
        self._halted = threading.Event()
        self._thread = spawn_thread(
            target=self._run, name="solo-consenter", kind="service"
        )

    def start(self) -> None:
        self._thread.start()

    def halt(self) -> None:
        self._halted.set()
        self._q.put(None)
        self._thread.join(timeout=5)

    def wait_ready(self) -> None:
        return

    def set_batch_timeout(self, seconds: float) -> None:
        """Adopt a committed BatchTimeout config change."""
        self._timeout = seconds

    def order(self, env: common_pb2.Envelope, config_seq: int = 0) -> None:
        if self._halted.is_set():
            raise RuntimeError("chain is halted")
        self._q.put(("normal", env.SerializeToString()))

    def configure(self, env: common_pb2.Envelope, config_seq: int = 0) -> None:
        if self._halted.is_set():
            raise RuntimeError("chain is halted")
        self._q.put(("config", env.SerializeToString()))

    def _emit(self, batch: list[bytes], is_config: bool = False) -> None:
        if not batch:
            return
        blk = self._writer.create_next_block(batch)
        self._writer.write_block(blk, is_config=is_config)
        self._on_block(blk)

    def _run(self) -> None:
        # the batch timer, as upstream's solo main loop keeps it: armed
        # by the message that enters an EMPTY batch, left alone by the
        # messages that follow it, disarmed by a cut that leaves
        # nothing pending.  `deadline` is when it fires (None: not
        # armed).  A per-`get` timeout would restart it with every
        # message, and a channel whose messages come less than
        # BatchTimeout apart would be cut by count alone.
        deadline: float | None = None
        while not self._halted.is_set():
            try:
                if deadline is None:
                    item = self._q.get()
                else:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        raise queue.Empty  # due: before the next message
                    item = self._q.get(timeout=wait)
            except queue.Empty:
                # batch timer fired
                self._emit(self._cutter.cut())
                deadline = None
                continue
            if item is None:
                break
            kind, raw = item
            if kind == "config":
                # config messages are isolated into their own block
                self._emit(self._cutter.cut())
                self._emit([raw], is_config=True)
                deadline = None
                continue
            batches, pending = self._cutter.ordered(raw)
            for batch in batches:
                self._emit(batch)
            if not pending:
                deadline = None
            elif deadline is None:
                deadline = time.monotonic() + self._timeout
        # drain on halt
        self._emit(self._cutter.cut())


__all__ = ["SoloChain"]
