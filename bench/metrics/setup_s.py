"""Process start to the first due request: weights, compile or cache load,
warm-up."""


def read(run):
    return run.setup_s
