"""CSP provider (`csp/tpu/provider.py`): how long a block's finish
waited for its signatures' mask, from
`validate_stage_seconds["verify_wait"]` over the window's blocks."""


def read(obs):
    if not obs["blocks"]:
        return None
    return 1e3 * obs["validate_stage_seconds"].get("verify_wait", 0.0) / obs["blocks"]
