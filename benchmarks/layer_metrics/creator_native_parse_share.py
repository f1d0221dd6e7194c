"""Validator (`peer/txvalidator.py`): of the creators the window's
blocks deserialised and validated afresh (`creator_validations` on the
`collect` spans), the share whose certificate one native call read
without the interpreter's lock (`creator_native_parse`), %.  The
engagement reading of the native certificate reader: near 100 in a cell
whose blocks are crowded with strangers, less what the MSP's deserialize
cache still held and what the reader handed back (another curve,
algorithm or encoding), and 0 where a block carries a creator or two.
A program whose spans lack the argument (before PR 50), and a window in
which nothing was validated, give nothing to read.

`# creator_parses` prints beside it, a block."""

from benchlib import spans


def read(obs):
    collects = [e["args"] for e in spans.named(obs, "collect")
                if "creator_native_parse" in e["args"]]
    validated = sum(a["creator_validations"] for a in collects)
    if not validated:
        return None
    native = sum(a["creator_native_parse"] for a in collects)
    n = len(collects)
    spans.say("creator_parses", {
        "blocks": n,
        "validated_per_block": validated / n,
        "read_natively_per_block": native / n,
        "chain_signatures_batched_per_block":
            sum(a.get("creator_chain_batch", 0) for a in collects) / n,
    })
    return 100.0 * native / validated
