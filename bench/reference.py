"""The plain reference: a float64 brute-force cross-match, the check that
holds the served answers to it, and the lower-precision control.

Copied from ``chip_smoke.py`` (``reference_join``/``check_results``) and
extended with per-query radii and magnitude cuts.  Nothing here imports
the program: buckets are recomputed from the catalog's HTM keys by an
argsort of its own, and the join is numpy over every object of each
bucket a probe's key range covers.

The served engine joins float32 coordinates; the reference joins the same
coordinates in float64.  ``DELTA`` covers the float32 arithmetic between
them: each dot sums three products of unit-vector components (at most
3 * 2**-24 rounding), and a TPU's HIGHEST precision builds the float32
product from six bf16 passes, dropping terms of up to 2 * 2**-24 and
rounding each partial sum.  8 * 2**-24, four float32 ulps at 1.0, bounds
that with room.  A decision that a dot this close to its threshold (or to
the runner-up) could flip is counted "in band", not checked.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "DELTA",
    "Record",
    "Layout",
    "Reference",
    "reference_join",
    "check_results",
    "control_results",
]

DELTA = 8 * 2.0**-24


@dataclasses.dataclass
class Record:
    """One query's matched probes from one bucket (the served engine's
    ``MatchResult`` has the same fields)."""

    query_id: int
    probe_idx: np.ndarray
    match_obj: np.ndarray
    best_dot: np.ndarray
    n_candidates: np.ndarray


class Layout:
    """Equal-count buckets over the HTM-sorted catalog."""

    def __init__(self, htm: np.ndarray, objects_per_bucket: int) -> None:
        self.opb = int(objects_per_bucket)
        self.order = np.argsort(htm, kind="stable")
        self.n_buckets = -(-len(self.order) // self.opb)
        self.first_keys = htm[self.order][np.arange(self.n_buckets) * self.opb]
        self.bucket_of_row = np.empty(len(self.order), np.int64)
        self.bucket_of_row[self.order] = np.arange(len(self.order)) // self.opb

    def rows(self, b: int) -> np.ndarray:
        return self.order[b * self.opb : (b + 1) * self.opb]

    def bucket_of(self, keys) -> np.ndarray:
        b = np.searchsorted(self.first_keys, keys, side="right") - 1
        return np.clip(b, 0, self.n_buckets - 1)

    def units(self, query) -> tuple[np.ndarray, np.ndarray]:
        """(probe, bucket) of every work unit: each probe against every
        bucket its key range covers."""
        lo, hi = self.bucket_of(query.keys_lo), self.bucket_of(query.keys_hi)
        span = hi - lo + 1
        probe = np.repeat(np.arange(len(lo)), span)
        step = np.arange(len(probe)) - np.repeat(np.cumsum(span) - span, span)
        return probe, lo[probe] + step


@dataclasses.dataclass
class Reference:
    """Per (query, probe, bucket) work unit, sorted by ``key``."""

    key: np.ndarray  # packed (query, probe, bucket), see _pack
    best_row: np.ndarray  # catalog row of the nearest object
    best_dot: np.ndarray  # its float64 dot
    n_above: np.ndarray  # pairs with dot > thr + DELTA
    n_band: np.ndarray  # pairs with |dot - thr| <= DELTA
    unique_best: np.ndarray  # no runner-up within DELTA of the best
    matched: np.ndarray  # best >= thr and the best object passes the cut
    firm: np.ndarray  # matched status cannot flip within DELTA


def _pack(qid, probe, bucket):
    return (
        (np.asarray(qid, np.int64) << 40)
        | (np.asarray(probe, np.int64) << 16)
        | np.asarray(bucket, np.int64)
    )


def _units(layout, queries, pred_of):
    """Flattened work units of ``queries``: query, probe, bucket, float32
    threshold, magnitude cut and probe position (float32, widened)."""
    parts = []
    for q in queries:
        probe, bucket = layout.units(q)
        thr, cut = pred_of(q)
        parts.append((
            np.full(len(probe), q.query_id), probe, bucket,
            np.full(len(probe), np.float32(thr)), np.full(len(probe), float(cut)),
            q.payload["positions"][probe],
        ))
    cols = [np.concatenate(c) for c in zip(*parts)]
    qid, probe, bucket, thr, cut, xyz = cols
    return (qid, probe, bucket, thr.astype(np.float64), cut,
            xyz.astype(np.float32).astype(np.float64))


def reference_join(catalog, layout: Layout, queries, pred_of, chunk=1024) -> Reference:
    """Brute-force float64 join of every probe against every object of each
    bucket its key range covers.  ``pred_of(query)`` gives the query's
    (cos threshold, magnitude cut); the threshold is rounded to float32,
    as the engine compares in float32."""
    qid, probe, bucket, thr, cut, xyz = _units(layout, queries, pred_of)
    pos = catalog.positions.astype(np.float32).astype(np.float64)
    mags = catalog.mags

    n = len(qid)
    best_row = np.zeros(n, np.int64)
    best_dot = np.zeros(n)
    n_above = np.zeros(n, np.int64)
    n_band = np.zeros(n, np.int64)
    unique_best = np.zeros(n, bool)
    mag_split = np.zeros(n, bool)
    for b in np.unique(bucket):
        rows = layout.rows(b)
        objs = pos[rows].T
        mag_b = mags[rows]
        units = np.nonzero(bucket == b)[0]
        for at in range(0, len(units), chunk):
            u = units[at : at + chunk]
            t = xyz[u] @ objs
            t -= thr[u, None]  # dot - thr
            j = np.argmax(t, axis=1)
            top = t[np.arange(len(u)), j]
            n_above[u] = np.count_nonzero(t > DELTA, axis=1)
            n_band[u] = np.count_nonzero(t >= -DELTA, axis=1) - n_above[u]
            tied = t >= (top - DELTA)[:, None]
            n_tied = np.count_nonzero(tied, axis=1)
            for r in np.nonzero(n_tied > 1)[0]:
                ok = mag_b[tied[r]] <= cut[u[r]]
                mag_split[u[r]] = ok.any() and not ok.all()
            best_row[u] = rows[j]
            best_dot[u] = top + thr[u]
            unique_best[u] = n_tied == 1
    matched = (best_dot >= thr) & (mags[best_row] <= cut)
    firm = (np.abs(best_dot - thr) > DELTA) & ~mag_split
    key = _pack(qid, probe, bucket)
    s = np.argsort(key)
    return Reference(
        key[s], best_row[s], best_dot[s], n_above[s], n_band[s],
        unique_best[s], matched[s], firm[s],
    )


def check_results(layout: Layout, results, ref: Reference) -> dict:
    """Compare routed matches (``{query_id: [records]}``) with the reference.

    The engine reports matched probes only.  Per work unit: the matched
    status must agree wherever it is firm; a reported probe's ``n_cand``
    must agree where no pair lies in the band, its object where the best
    is unique within DELTA; a reported probe that is no work unit of its
    query, or is reported twice, is wrong.  ``dot_err`` is the largest
    |best_dot - float64 dot| over the reported probes.
    """
    recs = [r for rs in results.values() for r in rs]
    if recs:
        key = np.concatenate(
            [_pack(r.query_id, r.probe_idx, layout.bucket_of_row[r.match_obj])
             for r in recs]
        )
        obj = np.concatenate([r.match_obj for r in recs])
        dot = np.concatenate([r.best_dot for r in recs]).astype(np.float64)
        cnt = np.concatenate([r.n_candidates for r in recs])
    else:
        key = obj = cnt = np.zeros(0, np.int64)
        dot = np.zeros(0)
    at = np.minimum(np.searchsorted(ref.key, key), max(len(ref.key) - 1, 0))
    known = (ref.key[at] == key) if len(ref.key) else np.zeros(len(key), bool)
    reported = np.zeros(len(ref.key), bool)
    reported[at[known]] = True
    at = at[known]
    err = np.abs(dot[known] - ref.best_dot[at])
    bad = {
        "unknown_or_duplicate": int(
            (~known).sum() + known.sum() - len(np.unique(at))
        ),
        "status": int((ref.firm & (reported != ref.matched)).sum()),
        "n_cand": int(
            ((ref.n_band[at] == 0) & (cnt[known] != ref.n_above[at])).sum()
        ),
        "best_idx": int(
            (ref.unique_best[at] & (obj[known] != ref.best_row[at])).sum()
        ),
    }
    return {
        "units": int(len(ref.key)),
        "matched": int(known.sum()),
        "in_band": int((~ref.firm).sum()),
        "wrong": sum(bad.values()),
        "by_check": bad,
        "dot_err": float(err.max()) if len(err) else 0.0,
    }


def _split_bf16(x):
    """x = hi + lo + r with hi and lo bfloat16 values (held in float32)."""
    import jax.numpy as jnp

    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _high_dots(p, o):
    """Dots as ``Precision.HIGH`` forms them: three bf16 products (hi*hi,
    hi*lo, lo*hi), each exact in float32, summed; lo*lo and the residuals
    are dropped.  Written out, so the CPU computes what the TPU would."""
    import jax
    import jax.numpy as jnp

    exact = jax.lax.Precision.HIGHEST
    ph, pl = _split_bf16(p)
    oh, ol = _split_bf16(o)
    return (jnp.dot(ph, oh.T, precision=exact)
            + jnp.dot(ph, ol.T, precision=exact)
            + jnp.dot(pl, oh.T, precision=exact))


def control_results(catalog, layout: Layout, queries, pred_of, chunk=1024):
    """The control: the same join put in the program's place, with float32
    dots at ``Precision.HIGH`` (three bf16 passes) where the configuration
    states HIGHEST (six).  Returns ``{query_id: [Record]}`` as the served
    engine routes it: matched probes only, ``n_cand > 0`` and the best
    object within the query's magnitude cut."""
    import jax.numpy as jnp

    qid, probe, bucket, thr, cut, xyz = _units(layout, queries, pred_of)
    pos32 = catalog.positions.astype(np.float32)
    out: dict[int, list[Record]] = {int(q.query_id): [] for q in queries}
    for b in np.unique(bucket):
        rows = layout.rows(b)
        objs = jnp.asarray(pos32[rows])
        units = np.nonzero(bucket == b)[0]
        for at in range(0, len(units), chunk):
            u = units[at : at + chunk]
            d = _high_dots(jnp.asarray(xyz[u].astype(np.float32)), objs)
            thr32 = jnp.asarray(thr[u].astype(np.float32))[:, None]
            j = np.asarray(jnp.argmax(d, axis=1))
            top = np.asarray(jnp.max(d, axis=1))
            cnt = np.asarray(jnp.sum(d >= thr32, axis=1))
            hit = (cnt > 0) & (catalog.mags[rows[j]] <= cut[u])
            for q in np.unique(qid[u[hit]]):
                sel = hit & (qid[u] == q)
                out[int(q)].append(Record(
                    int(q), probe[u[sel]], rows[j[sel]], top[sel], cnt[sel],
                ))
    return out
