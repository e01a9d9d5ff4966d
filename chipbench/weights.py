"""Weights from the seed, made by the benchmark and handed to the
program and to the plain reference alike (the reference takes nothing
that the program has made).

A reference module lists its leaves as ``{name: (shape, kind, scale)}``:
``normal`` draws N(0, scale²), ``gain`` draws 1 + N(0, scale²) (norm
gains and BN scales: a program that dropped them would show), ``zeros``
is what it says.  Leaves are drawn on the host, in float32, each from a
generator of its own keyed by (seed, index), a few at a time on threads
(numpy's generators release the interpreter lock).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

Spec = Dict[str, Tuple[tuple, str, float]]


def _draw(seed: int, index: int, shape, kind: str, scale: float):
    if kind == "zeros":
        return np.zeros(shape, np.float32)
    rng = np.random.default_rng([int(seed) % (2 ** 63), index])
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= np.float32(scale)
    if kind == "gain":
        x += np.float32(1.0)
    elif kind != "normal":
        raise ValueError(f"unknown leaf kind {kind!r}")
    return x


def make(spec: Spec, seed: int, threads: int = 8) -> Dict[str, np.ndarray]:
    names = list(spec)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        arrays = list(pool.map(
            lambda i: _draw(seed, i, *spec[names[i]]), range(len(names))))
    return dict(zip(names, arrays))
