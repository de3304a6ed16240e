"""Process start to the first timed operation: imports, device start,
data made from the seed, the set-up the traffic needs and the warm-up."""


def read(run):
    return run.setup_s
