"""A provider that does not verify what is too small to send to the
device: a batch under `min_device_batch` is answered all true.  Breaks
"a batch too small for the device is verified on the host to the same
rule; no signature is skipped for its batch's size": the corrupted
signatures planted in blocks of one to three transactions then pass,
and their writes land in the state.  Larger batches go where they
went."""


def apply():
    from fabric_tpu.csp.tpu.provider import TPUCSP

    inner = TPUCSP.verify_batch_async

    def verify_batch_async(self, items, flush=False):
        if len(items) < self._min_device_batch:
            self._note_sealed("small", len(items))
            return lambda: [True] * len(items)
        return inner(self, items, flush)

    TPUCSP.verify_batch_async = verify_batch_async
