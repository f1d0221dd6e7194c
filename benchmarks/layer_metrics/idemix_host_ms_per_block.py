"""Idemix provider: the host half of a flush per block: `idemix.prepare`
(validity, scalars, limb packing) + `idemix.normalize` (the copy's
unpacking and the batched inversion) + `idemix.rehash` (Fiat-Shamir) +
`idemix.pairing` (the random linear combination and two pairings; the
per-item isolation where the combined check fails).  It runs on the
flush's worker thread, beside the main thread's `collect`: both hold
the interpreter's lock.  The four are printed as `# idemix_shares`."""

from benchlib import spans

PARTS = ("idemix.prepare", "idemix.normalize", "idemix.rehash", "idemix.pairing")


def read(obs):
    if not isinstance(obs.get("spans"), list) or not obs["blocks"]:
        return None
    n = obs["blocks"]
    per = {name: spans.total_ms(spans.named(obs, name)) / n for name in PARTS}
    per["idemix.enqueue"] = spans.total_ms(spans.named(obs, "idemix.enqueue")) / n
    per["idemix.device_wait"] = spans.total_ms(spans.named(obs, "idemix.device_wait")) / n
    spans.say("idemix_shares", per)
    return sum(per[name] for name in PARTS)
