"""Peak bytes on the fullest chip (``harness.memory_peak_bytes``: the
allocator's peak in use plus what it reserved for the compiled
programs' scratch), GB, read when the window closes and before the
reference runs."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 or None
