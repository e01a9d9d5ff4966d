"""Peak of ``PagePool.used_pages()`` as the load generator saw it at
each poll, over the pool's capacity, percent."""


def read(run):
    return run.get("pool_peak_share") or None
