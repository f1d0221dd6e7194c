"""When a timing of `idemix-nym128` is a timing of the Idemix path on
the device: every credential proof and pseudonym signature had its
commitments computed by the Pallas BN254 kernel, none by a fallback.

The provider chooses the host under its crossover or off a TPU, falls
from the Pallas ladder to the XLA scan and from the device to the host
on an error, each with a log line: a run that timed any of those would
read the same flags.  So, from the program's own counts
(`IdemixCSP.tally()`, from process start, warm-up included: the right
span for a thing that may never happen):

    idemix_items_not_on_the_pallas_kernel   items by any path but `pallas`
    idemix_fallbacks                        fallbacks of any reason

and from its record of the last batches (`recent_batches()`), the
window's being the last `len(yielded)` of them, one a block:

    bn254_buckets_first_seen_in_window      a bucket no earlier batch ran at
    idemix_proofs_short_on_device           ) per yielded block, one proof and one
    idemix_nyms_short_on_device             ) pseudonym signature a transaction whose
                                              creator the peer did not refuse when it
                                              deserialised it, less what the kernel
                                              verified of that block's batch

each with limit 0."""


def numbers(cell) -> dict:
    idemix = cell.csp.idemix
    tally = idemix.tally()
    off_kernel = sum(
        n for key, n in tally["items"].items() if not key.endswith(".pallas")
    )
    recent = idemix.recent_batches()
    n = len(cell.yielded)
    window = recent[len(recent) - n:] if n else []
    before = recent[:len(recent) - n]
    new = {b["bucket"] for b in window if b["bucket"]} - {b["bucket"] for b in before}
    refused = cell.world.refused_at_deserialise
    short_proofs = short_nyms = 0
    for k, (bno, flags) in enumerate(cell.yielded):
        due = len(flags) - refused[bno]
        batch = window[k] if k < len(window) else {"proofs": 0, "nyms": 0, "path": ""}
        on_kernel = batch["path"] == "pallas"
        short_proofs += max(0, due - (batch["proofs"] if on_kernel else 0))
        short_nyms += max(0, due - (batch["nyms"] if on_kernel else 0))
    return {
        "idemix_items_not_on_the_pallas_kernel": (off_kernel, 0),
        "idemix_fallbacks": (sum(tally["fallbacks"].values()), 0),
        "bn254_buckets_first_seen_in_window": (len(new), 0),
        "idemix_proofs_short_on_device": (short_proofs, 0),
        "idemix_nyms_short_on_device": (short_nyms, 0),
    }
