"""Idemix provider: wall of the `idemix.flush` spans (dispatch begun
to mask sealed: host packing, the launch, the kernel at its bucket,
the copy back, the batched inversion, the challenge re-hash, the
pairings) per block of the window; a block has one."""

from benchlib import spans


def read(obs):
    if not isinstance(obs.get("spans"), list) or not obs["blocks"]:
        return None
    return spans.total_ms(spans.named(obs, "idemix.flush")) / obs["blocks"]
