"""Process start to the first timed round: weights, mask calibration,
server, the first rounds (compile-cache loads included)."""


def read(run):
    return run["setup_s"]
