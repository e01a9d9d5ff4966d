"""Programs that reached the backend compiler (or its cache) between
the first measured instant and the window's close.  Anything but 0 is a
fault of the warm-up."""


def read(run):
    t0 = run["ctx"]["t_process"] + run["setup_s"]
    return float(run["meter"].compiles_between(t0, run["window"][1]))
