"""``jax.monitoring`` trace + lower + backend-compile durations that
fell in set-up."""


def read(run):
    t0 = run["ctx"]["t_process"]
    return run["meter"].seconds_between(t0, t0 + run["setup_s"])
