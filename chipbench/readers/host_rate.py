"""All the work of the window over all its seconds (host clock; the
window's last step or token is fenced before the clock is read)."""


def read(run):
    return run["work"] / run["window_s"] if run["window_s"] > 0 else None
