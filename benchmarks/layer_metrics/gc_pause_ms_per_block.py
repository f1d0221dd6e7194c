"""Host runtime: summed pause of generation-2 collections inside the
timed passes, per block, from the harness's own `gc.callbacks` entry."""


def read(obs):
    if not obs["blocks"]:
        return None
    return 1e3 * obs["gc_gen2_pause_timed_s"] / obs["blocks"]
