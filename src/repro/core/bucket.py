"""Bucket partitioning: equal-sized, spatially-coherent units of work.

Paper §3.1: relational tables are partitioned into equal-sized (same number
of objects) buckets along the HTM space-filling curve.  Each bucket covers a
contiguous key range, so (a) bucket I/O cost is uniform, (b) spatial
proximity is preserved and joins localize inside a bucket, and (c) a query's
key-range bounding box maps to a small set of overlapping buckets.

``Partitioner`` is data-structure only (host-side numpy); the actual object
payloads live in a ``BucketStore`` that the engines read through the
``BucketCache``.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Optional, Sequence

import numpy as np

__all__ = ["BucketSpec", "Partitioner", "BucketStore"]


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """One bucket: a contiguous SFC-key range holding ``count`` objects."""

    bucket_id: int
    key_lo: int  # inclusive
    key_hi: int  # exclusive
    count: int
    nbytes: int  # simulated storage footprint (uniform by construction)


class Partitioner:
    """Equal-count partition of a sorted key space into buckets.

    Parameters
    ----------
    keys:
        SFC keys of every object in the table (need not be sorted).
    objects_per_bucket:
        Paper uses 10,000 objects => ~40 MB buckets on SDSS.
    bytes_per_object:
        Only used to report the simulated bucket size.
    """

    def __init__(
        self,
        keys: np.ndarray,
        objects_per_bucket: int = 10_000,
        bytes_per_object: int = 4_096,
    ) -> None:
        keys = np.asarray(keys, dtype=np.uint64)
        order = np.argsort(keys, kind="stable")
        self.sorted_keys = keys[order]
        self.order = order  # original-index permutation, sorted by key
        self.objects_per_bucket = int(objects_per_bucket)
        self.bytes_per_object = int(bytes_per_object)
        n = len(keys)
        self.n_buckets = max(1, -(-n // self.objects_per_bucket))
        # Boundaries are the keys at each bucket's first object.
        starts = np.arange(self.n_buckets) * self.objects_per_bucket
        self._start_idx = starts
        self._boundary_keys = self.sorted_keys[starts]
        self._layout_pos: dict[int, float] = {}  # layout_position cache
        self.specs: list[BucketSpec] = []
        for b in range(self.n_buckets):
            lo = int(self._boundary_keys[b])
            hi = (
                int(self._boundary_keys[b + 1])
                if b + 1 < self.n_buckets
                else int(self.sorted_keys[-1]) + 1
            )
            i0 = starts[b]
            i1 = min(n, i0 + self.objects_per_bucket)
            self.specs.append(
                BucketSpec(
                    bucket_id=b,
                    key_lo=lo,
                    key_hi=hi,
                    count=int(i1 - i0),
                    nbytes=int(i1 - i0) * self.bytes_per_object,
                )
            )

    # -- lookup ------------------------------------------------------------
    def bucket_of_keys(self, keys: np.ndarray) -> np.ndarray:
        """Bucket id for each key (vectorized binary search)."""
        keys = np.asarray(keys, dtype=np.uint64)
        idx = np.searchsorted(self._boundary_keys, keys, side="right") - 1
        return np.clip(idx, 0, self.n_buckets - 1).astype(np.int64)

    def buckets_for_range(self, key_lo: int, key_hi: int) -> np.ndarray:
        """All bucket ids whose key range overlaps [key_lo, key_hi]."""
        b0 = int(self.bucket_of_keys(np.array([key_lo]))[0])
        b1 = int(self.bucket_of_keys(np.array([key_hi]))[0])
        return np.arange(b0, b1 + 1, dtype=np.int64)

    def object_slice(self, bucket_id: int) -> np.ndarray:
        """Original-table indices of the objects stored in ``bucket_id``."""
        i0 = self._start_idx[bucket_id]
        i1 = min(len(self.sorted_keys), i0 + self.objects_per_bucket)
        return self.order[i0:i1]

    def layout_position(self, bucket_id: int) -> float:
        """Physical file position of the bucket: the mean *original-table*
        row address of its objects (its SFC run gathered back to where the
        rows actually sit).  The table was written in ingest order, not
        SFC order, so bucket id (SFC run) and file position are different
        axes — an elevator sweep that seeks by id zig-zags across the
        file.  This is the ``layout_of`` the prefetch planner's sweep
        should order by (ScanPlanConfig.layout_of)."""
        pos = self._layout_pos.get(bucket_id)
        if pos is None:
            idx = self.object_slice(bucket_id)
            pos = float(idx.mean()) if len(idx) else float(bucket_id)
            self._layout_pos[bucket_id] = pos
        return pos


# A read into a frame of at least this many bytes is split by rows over
# the copy pool: 40 MB SkyQuery buckets are, 400 KB test buckets are not.
SPLIT_BYTES = 4 << 20
_COPY_WORKERS = min(4, os.cpu_count() or 1)
_copy_pool: Optional[ThreadPoolExecutor] = None
_copy_pool_lock = threading.Lock()


def _pool() -> ThreadPoolExecutor:
    """The process's bucket-copy threads, started on the first split read
    (``np.take`` on a plain dtype releases the GIL, so they copy in
    parallel)."""
    global _copy_pool
    with _copy_pool_lock:
        if _copy_pool is None:
            _copy_pool = ThreadPoolExecutor(
                _COPY_WORKERS, thread_name_prefix="bucket-copy"
            )
        return _copy_pool


class BucketStore:
    """Holds per-bucket object payloads (host numpy; the 'disk').

    ``payload`` is any dict of equal-length arrays (e.g. unit vectors +
    attributes).  Reads go through ``repro.core.cache.BucketCache``.
    """

    def __init__(self, partitioner: Partitioner, payload: dict[str, np.ndarray]):
        self.partitioner = partitioner
        self._payload = payload
        lengths = {k: len(v) for k, v in payload.items()}
        assert len(set(lengths.values())) <= 1, f"ragged payload: {lengths}"
        # ``read(out=)`` gathers with mode="clip", which trusts the
        # partitioner's row numbers to lie inside the table.
        n = len(partitioner.order)
        if any(length != n for length in lengths.values()):
            raise ValueError(f"payload lengths {lengths} != {n} keys")

    def read(
        self, bucket_id: int, out: Optional[dict[str, np.ndarray]] = None
    ) -> dict[str, np.ndarray]:
        """Every column of the bucket's objects, in key order.

        Without ``out`` the copy lands in new arrays.  ``out`` (arrays
        shaped like this bucket's payload, e.g. the first ``spec(b).count``
        rows of an ``empty_frame``) is filled in place and returned: the
        same bytes, copied into pages that are already mapped.  A frame of
        ``SPLIT_BYTES`` or more is copied in row chunks on the copy pool.
        """
        idx = self.partitioner.object_slice(bucket_id)
        if out is None:
            return {k: v[idx] for k, v in self._payload.items()}

        def take(lo: int, hi: int) -> None:
            # mode="clip": the default "raise" buffers ``out``, a second copy.
            for k, v in self._payload.items():
                np.take(v, idx[lo:hi], axis=0, mode="clip", out=out[k][lo:hi])

        n = len(idx)
        if _COPY_WORKERS == 1 or sum(a.nbytes for a in out.values()) < SPLIT_BYTES:
            take(0, n)
            return out
        edges = [n * i // _COPY_WORKERS for i in range(_COPY_WORKERS + 1)]
        pool = _pool()
        futures = [
            pool.submit(take, lo, hi) for lo, hi in zip(edges[1:-1], edges[2:])
        ]
        try:
            take(edges[0], edges[1])
        finally:
            wait(futures)
        for f in futures:
            f.result()
        return out

    def empty_frame(self) -> dict[str, np.ndarray]:
        """Uninitialised arrays with the rows of the largest bucket, one per
        payload column: a frame that ``read(b, out=...)`` can fill."""
        rows = max(s.count for s in self.partitioner.specs)
        return {
            k: np.empty((rows,) + v.shape[1:], v.dtype)
            for k, v in self._payload.items()
        }

    @property
    def n_buckets(self) -> int:
        return self.partitioner.n_buckets

    def spec(self, bucket_id: int) -> BucketSpec:
        return self.partitioner.specs[bucket_id]


def equal_count_edges(values: Sequence[float], n_buckets: int) -> np.ndarray:
    """Generic helper: quantile edges giving ~equal-count buckets."""
    qs = np.linspace(0.0, 1.0, n_buckets + 1)
    return np.quantile(np.asarray(values), qs)
