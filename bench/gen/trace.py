"""The benchmark's query stream: SkyQuery-style cross-match queries.

Copied from the program's ``repro.crossmatch.trace.make_trace`` (Zipf
hotspots with temporal locality, cones of lognormal size and radius, a
share of full-sky queries, Poisson arrivals, HTM bounding ranges of
``match_level_offset`` levels), with one change: every seed gets the same
work.  Make_trace draws each query's size, kind and gap on its own, so the
number of full-sky queries in a window, and with it the tail, moved with
the seed.  Here the stream is cut into blocks of ``block`` queries, and
every block holds the same multiset of sizes, cone radii, hotspot flags,
predicates, full-sky queries and inter-arrival gaps, taken at fixed
quantiles of make_trace's distributions.  The seed permutes each of those
within each block, places the hotspots and draws every position.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

from repro.core.workload import Query

from .catalog import normalize
from .htm import htm_ids

__all__ = ["Stream", "cone_sample", "block_shapes", "make_stream"]

# Full-sky sizes cycle over eight quantiles of their lognormal, in this
# fixed order, one block after another.
_FULLSKY_ORDER = (3, 6, 1, 4, 7, 2, 5, 0)


@dataclasses.dataclass
class Stream:
    """Queries in submission order; ``gaps[i]`` is the wait in seconds
    between the due times of queries i - 1 and i (open loops)."""

    queries: list[Query]
    gaps: np.ndarray


def cone_sample(center: np.ndarray, radius: float, n: int, rng) -> np.ndarray:
    """Uniform sample of ``n`` unit vectors within angular ``radius`` of center."""
    z = rng.uniform(np.cos(radius), 1.0, size=n)
    phi = rng.uniform(0.0, 2 * np.pi, size=n)
    r = np.sqrt(1 - z**2)
    local = np.stack([r * np.cos(phi), r * np.sin(phi), z], axis=-1)
    c = center / np.linalg.norm(center)
    if abs(c[2]) > 0.9999:
        return local if c[2] > 0 else local * np.array([1.0, 1.0, -1.0])
    axis = np.cross([0.0, 0.0, 1.0], c)
    axis = axis / np.linalg.norm(axis)
    ang = np.arccos(np.clip(c[2], -1, 1))
    K = np.array(
        [[0, -axis[2], axis[1]], [axis[2], 0, -axis[0]], [-axis[1], axis[0], 0]]
    )
    R = np.eye(3) + np.sin(ang) * K + (1 - np.cos(ang)) * (K @ K)
    return normalize(local @ R.T)


def _quantiles(n: int) -> np.ndarray:
    """Standard normal quantiles at the midpoints of ``n`` equal shares."""
    nd = statistics.NormalDist()
    return np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])


def block_shapes(mix: dict) -> dict[str, np.ndarray]:
    """One block's multiset, the same for every seed: per slot its kind
    (full-sky or cone), probe count, cone radius, hotspot flag, predicate
    (radius and magnitude cut, NaN where the mix sets none) and the gap
    before it.  Full-sky sizes are filled in per block by the caller."""
    b = int(mix["block"])
    n_fs = int(round(b * mix["fullsky_frac"]))
    n_cone = b - n_fs
    z = _quantiles(n_cone)
    sizes = np.exp(math.log(mix["objects_median"]) + mix["objects_sigma"] * z)
    radius = np.exp(math.log(mix["cone_radius_med"]) + mix["cone_radius_sigma"] * z)
    hot = np.arange(n_cone) < int(round(mix["hotspot_frac"] * n_cone))
    radii = mix.get("radii_rad")
    cuts = mix.get("mag_cuts")
    if radii:
        combos = [(r, m) for m in cuts for r in radii]
        pred = np.array([combos[i % len(combos)] for i in range(b)], float)
    else:
        pred = np.full((b, 2), np.nan)
    rate = mix.get("rate_per_s")
    u = (np.arange(b) + 0.5) / b
    gaps = -np.log1p(-u) / rate if rate else np.zeros(b)
    return {
        "fullsky": np.arange(b) < n_fs,
        "cone_sizes": np.maximum(sizes.astype(np.int64), 1),
        "cone_radius": radius,
        "hot": hot,
        "pred": pred,
        "gaps": gaps,
    }


def _fullsky_size(mix: dict, block_idx: int) -> int:
    q = (_FULLSKY_ORDER[block_idx % len(_FULLSKY_ORDER)] + 0.5) / len(_FULLSKY_ORDER)
    z = statistics.NormalDist().inv_cdf(q)
    median = mix["objects_median"] * mix["fullsky_size_factor"]
    return max(int(math.exp(math.log(median) + mix["objects_sigma"] * z)), 1)


def _draw(mix: dict, seed: int):
    """Positions and per-query fields of the whole stream, before HTM."""
    rng = np.random.default_rng([seed, 0x7CE])
    shapes = block_shapes(mix)
    b = int(mix["block"])
    n_blocks = -(-int(mix["n_queries"]) // b)
    hot_centers = normalize(rng.normal(size=(int(mix["n_hotspots"]), 3)))
    w = 1.0 / np.arange(1, int(mix["n_hotspots"]) + 1) ** mix["zipf_s"]
    probs = w / w.sum()
    prev_hotspot = 0
    pos_parts, fullsky, pred, gaps = [], [], [], []
    for j in range(n_blocks):
        slot_fs = rng.permutation(shapes["fullsky"])
        sizes = rng.permutation(shapes["cone_sizes"])
        radius = rng.permutation(shapes["cone_radius"])
        hot = rng.permutation(shapes["hot"])
        pred.append(rng.permutation(shapes["pred"]))
        gaps.append(rng.permutation(shapes["gaps"]))
        c = 0  # next cone slot
        for is_fs in slot_fs:
            if is_fs:
                n = _fullsky_size(mix, j)
                pos = normalize(rng.normal(size=(n, 3)))
            else:
                if hot[c]:
                    if rng.random() < mix["temporal_locality"]:
                        h = prev_hotspot
                    else:
                        h = int(rng.choice(len(probs), p=probs))
                    prev_hotspot = h
                    center = hot_centers[h]
                else:
                    center = normalize(rng.normal(size=3))
                pos = cone_sample(center, min(radius[c], np.pi), int(sizes[c]), rng)
                c += 1
            pos_parts.append(pos)
            fullsky.append(bool(is_fs))
    n = int(mix["n_queries"])
    counts = np.array([len(p) for p in pos_parts[:n]], np.int64)
    return {
        "positions": np.concatenate(pos_parts[:n]),
        "offsets": np.concatenate([[0], np.cumsum(counts)]),
        "fullsky": np.array(fullsky[:n]),
        "pred": np.concatenate(pred)[:n],
        "gaps": np.concatenate(gaps)[:n],
    }


def make_stream(mix: dict, level: int, seed: int, workers: int = 1) -> Stream:
    """The mix's query stream for a catalog indexed at HTM ``level``."""
    a = _draw(mix, seed)
    shift = np.uint64(2 * int(mix["match_level_offset"]))
    anc = htm_ids(a["positions"], level, workers) >> shift
    a["keys_lo"] = anc << shift
    a["keys_hi"] = ((anc + np.uint64(1)) << shift) - np.uint64(1)
    off = a["offsets"]
    queries = []
    for i in range(len(off) - 1):
        s = slice(int(off[i]), int(off[i + 1]))
        meta = {"fullsky": bool(a["fullsky"][i])}
        r, m = a["pred"][i]
        if not np.isnan(r):
            meta.update(radius=float(r), mag_cut=float(m))
        queries.append(
            Query(
                query_id=i,
                arrival_time=0.0,
                keys_lo=a["keys_lo"][s],
                keys_hi=a["keys_hi"][s],
                payload={"positions": a["positions"][s]},
                meta=meta,
            )
        )
    return Stream(queries=queries, gaps=a["gaps"])
