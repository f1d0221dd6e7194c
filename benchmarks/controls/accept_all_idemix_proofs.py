"""An Idemix provider that seals every mask all true: no credential
proof and no pseudonym signature can fail.  Breaks "every creator's
association proof is verified ..., pairings included" and "every
envelope's pseudonym signature is verified": the tampered proofs, the
signatures over another payload and the rogue issuer's proof then
pass, and their writes land in the state.  The batches still run, so
the provider's own counts stay as they were."""


def apply():
    from fabric_tpu.csp.idemix_provider import IdemixCSP

    inner = IdemixCSP._seal
    IdemixCSP._seal = (
        lambda self, items, mask, path, lanes, bucket:
        inner(self, items, [True] * len(mask), path, lanes, bucket)
    )
