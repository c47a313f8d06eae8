"""The benchmark's catalog: clustered sky positions and magnitudes from the
seed, bucketed by the program's own HTM index, partitioner and store.

The draw of positions and magnitudes is copied from the program's
``repro.crossmatch.catalog.make_catalog`` (with ``repro.core.sfc``'s
``unit_vectors``/``_normalize``), so that a later change there cannot move
the benchmark's data.  ``htm_id``, ``Partitioner`` and ``BucketStore``
stay the program's: loading the catalog is part of the system under test.

Each stored object is as wide as the deployment's: beside its position,
magnitude and HTM id (40 bytes) the store holds an opaque ``row`` column
that brings it to ``bucket_bytes / objects_per_bucket`` bytes, so a bucket
read and the bucket cache move the bytes the deployment moves.
"""
from __future__ import annotations

import numpy as np

from repro.core.bucket import BucketStore, Partitioner
from repro.crossmatch.catalog import SkyCatalog

from .htm import htm_ids

__all__ = ["normalize", "draw_catalog", "build_catalog", "n_objects", "row_bytes"]


def normalize(v: np.ndarray) -> np.ndarray:
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def _unit_vectors(n: int, seed: int) -> np.ndarray:
    return normalize(np.random.default_rng(seed).normal(size=(n, 3)))


def n_objects(cfg: dict) -> int:
    return int(cfg["n_buckets"]) * int(cfg["objects_per_bucket"])


def row_bytes(cfg: dict) -> int:
    """Bytes of one stored object, as the configuration's bucket states."""
    return int(cfg["bucket_bytes"]) // int(cfg["objects_per_bucket"])


def draw_catalog(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Positions (n, 3) float64 unit vectors and magnitudes (n,): a share
    ``cluster_frac`` in ``n_clusters`` Gaussian blobs of angular sigma
    ``cluster_scale`` rad, the rest uniform over the sphere."""
    n = n_objects(cfg)
    rng = np.random.default_rng(seed)
    n_cl = int(n * cfg["cluster_frac"])
    uni = _unit_vectors(n - n_cl, seed + 1)
    centers = _unit_vectors(int(cfg["n_clusters"]), seed + 2)
    which = rng.integers(0, int(cfg["n_clusters"]), size=n_cl)
    pts = centers[which] + rng.normal(scale=cfg["cluster_scale"], size=(n_cl, 3))
    positions = np.concatenate([uni, normalize(pts)], axis=0)
    rng.shuffle(positions, axis=0)
    mags = rng.uniform(cfg["mag_lo"], cfg["mag_hi"], size=n)
    return positions, mags


def build_catalog(cfg: dict, seed: int, workers: int = 1) -> SkyCatalog:
    """The configuration's catalog for ``seed``."""
    positions, mags = draw_catalog(cfg, seed)
    htm = htm_ids(positions, int(cfg["htm_level"]), workers)
    payload = {"positions": positions, "mags": mags, "htm": htm}
    narrow = sum(a.itemsize * (a.size // len(a)) for a in payload.values())
    payload["row"] = np.full((len(positions), row_bytes(cfg) - narrow), 0x5A, np.uint8)
    part = Partitioner(htm, objects_per_bucket=int(cfg["objects_per_bucket"]))
    return SkyCatalog(
        positions=positions,
        mags=mags,
        htm=htm,
        partitioner=part,
        store=BucketStore(part, payload),
        level=int(cfg["htm_level"]),
    )
