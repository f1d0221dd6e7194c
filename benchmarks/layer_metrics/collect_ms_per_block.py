"""Validator (`peer/txvalidator.py`): host collect per block, from
`validate_stage_seconds["collect"]` summed over the window's blocks."""


def read(obs):
    if not obs["blocks"]:
        return None
    return 1e3 * obs["validate_stage_seconds"].get("collect", 0.0) / obs["blocks"]
