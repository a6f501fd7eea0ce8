"""Process start to the first timed step: imports, device start, weights,
g^0 and batches, compile or cache load, and the first three steps."""


def read(run):
    return run["setup_s"]
