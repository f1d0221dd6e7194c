"""Idemix provider: credential proofs sent through per-item pairings
because a batch's combined pairing check failed (`idemix.pairing`
`isolated`), per pass of the window.  One block of a pass carries a
proof of a rogue issuer, so a block's worth is expected, and 0.0 where
every combined check passed."""

from benchlib import spans


def read(obs):
    passes = len(obs.get("pass_walls_s") or ())
    if not isinstance(obs.get("spans"), list) or not passes:
        return None
    pairings = spans.named(obs, "idemix.pairing")
    return sum(e["args"].get("isolated", 0) for e in pairings) / passes
