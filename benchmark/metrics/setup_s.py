"""Process start to the start of the measured window: loading, the replica
or trainer coming up, warm-up, and in a first run compilation. host_clock."""


def reduce(run):
    return run["setup_s"]
