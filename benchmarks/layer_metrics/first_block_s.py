"""CSP provider: wall of the process's first device dispatch, which is
Python trace and lower of the kernel plus its compile or cache load."""


def read(obs):
    return obs["first_block_s"]
