"""A provider that checks no signature: every mask is all true.
Breaks "every creator signature and every endorsement signature is
checked"; the planted corruptions then pass, and their writes land
in the state."""


def apply():
    from fabric_tpu.csp.tpu.provider import TPUCSP

    # the provider's own signature: a lone block hands its first chunk
    # over with `flush=True`, and there is nothing here to flush
    TPUCSP.verify_batch_async = (
        lambda self, items, flush=False: (lambda: [True] * len(items))
    )
