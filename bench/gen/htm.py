"""The program's HTM index over many points, in worker processes.

``repro.core.sfc.htm_id`` is pure numpy on one core; over the 2,000,000
objects of a catalog it takes about half a minute, most of a run's set-up.
The points are split into chunks that worker processes index; the workers
import numpy and ``repro.core.sfc`` only and never touch JAX, so the
parent keeps the chip.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing

import numpy as np

from repro.core.sfc import htm_id

__all__ = ["htm_ids"]

_CHUNK = 65_536  # points per task


def htm_ids(points: np.ndarray, level: int, workers: int = 1) -> np.ndarray:
    """``htm_id(points, level)``, computed by ``workers`` processes."""
    n = len(points)
    if workers <= 1 or n <= _CHUNK:
        return htm_id(points, level=level)
    chunks = [points[i : i + _CHUNK] for i in range(0, n, _CHUNK)]
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        parts = list(pool.map(htm_id, chunks, [level] * len(chunks)))
    return np.concatenate(parts)
