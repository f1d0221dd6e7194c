"""Validator (`peer/txvalidator.py`): the endorsement policies' own
evaluation per block, once the signatures' mask is there, from
`validate_stage_seconds["policy"]` over the window's blocks.  With
`collect` and `verify_wait` it is the validator's whole wall."""


def read(obs):
    if not obs["blocks"]:
        return None
    return 1e3 * obs["validate_stage_seconds"].get("policy", 0.0) / obs["blocks"]
