"""Process start to the first measured instant (host clock)."""


def read(run):
    return run["setup_s"]
