"""CSP provider (`csp/tpu/provider.py`): mean wall of a `tpu.dispatch`
span: the host's work to put one flush on the device (native marshal,
key table, slicing and padding, `device_put`, the kernel enqueue).  A
dispatch that held a `cold` enqueue (trace, lower, compile) is left
out.  The shares of `tpu.marshal`, `tpu.keytable` and `tpu.enqueue` in
it are printed beside it."""

from benchlib import spans

PARTS = ("tpu.marshal", "tpu.keytable", "tpu.enqueue")


def read(obs):
    by_dispatch = spans.by_parent(obs, *PARTS)
    warm, parts = [], []
    for d in spans.named(obs, "tpu.dispatch"):
        mine = by_dispatch.get(d["args"].get("span"), [])
        if not any(e["args"].get("cold") for e in mine):
            warm.append(d)
            parts.extend(mine)
    if not warm:
        return None
    total = spans.total_ms(warm)
    if total > 0:
        spans.say("dispatch_shares", {
            name: spans.total_ms(e for e in parts if e["name"] == name) / total
            for name in PARTS
        })
    return total / len(warm)
