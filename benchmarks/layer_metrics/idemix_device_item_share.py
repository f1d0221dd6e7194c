"""Idemix provider: of the Idemix items (credential proofs and
pseudonym signatures) the window's `idemix.flush` spans sealed, the
share whose commitments the Pallas BN254 kernel computed (`path`
pallas).  100, or the condition `idemix-on-device` has already said
`correct` false."""

from benchlib import spans


def read(obs):
    flushes = spans.named(obs, "idemix.flush")
    items = sum(e["args"].get("proofs", 0) + e["args"].get("nyms", 0) for e in flushes)
    if not items:
        return None
    on_kernel = sum(
        e["args"].get("proofs", 0) + e["args"].get("nyms", 0)
        for e in flushes if e["args"].get("path") == "pallas"
    )
    return 100.0 * on_kernel / items
