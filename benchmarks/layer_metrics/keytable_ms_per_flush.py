"""CSP provider (`csp/tpu/provider.py`): mean wall of a warm
`tpu.keytable` span: what a flush pays to learn where its lanes' keys
go (the table as it stands, the table grown or reset, or, past the
table's size, a key a lane).  A span under a dispatch that held a
`cold` enqueue is left out, as `dispatch_ms_per_flush` leaves the
dispatch out.  `# keytable_outcomes` prints how the window's flushes
came out and their mean distinct keys."""

from benchlib import spans


def read(obs):
    cold = {
        e["args"].get("parent") for e in spans.named(obs, "tpu.enqueue")
        if e["args"].get("cold")
    }
    warm = [
        e for e in spans.named(obs, "tpu.keytable")
        if e["args"].get("parent") not in cold
    ]
    if not warm:
        return None
    outcomes: dict = {}
    for e in warm:
        outcome = e["args"].get("outcome", "not_said")
        outcomes[outcome] = outcomes.get(outcome, 0) + 1
    known = [e["args"]["distinct"] for e in warm if "distinct" in e["args"]]
    spans.say("keytable_outcomes", {
        "flushes": outcomes,
        "mean_distinct_keys": sum(known) / len(known) if known else None,
    })
    return spans.total_ms(warm) / len(warm)
