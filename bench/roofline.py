"""The work the bucket-scan cross-match defines, and the least time a chip
could do it in.

The yardstick is the join's own, whatever a kernel computes or skips:
each probe of a round is compared with every object of its own bucket
(segment), three multiply-adds a pair.  Bytes are each operand read once
(probes and objects, three float32 coordinates each; a per-probe float32
threshold where the plan carries one) and the outputs written once (best
index, best dot and candidate count, four bytes each, per probe).  Padding,
masked pairs of other segments and repeated reads of a bucket are the
kernel's overhead and are not counted.
"""
from __future__ import annotations

import json
import pathlib

__all__ = ["pair_work", "least_seconds", "load_peaks"]

FLOPS_PER_PAIR = 6  # three multiply-adds
COORD_BYTES = 3 * 4  # three float32 coordinates
OUT_BYTES = 3 * 4  # best index, best dot, candidate count
THR_BYTES = 4  # per-probe float32 threshold

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def pair_work(segments, per_probe_threshold: bool = False) -> tuple[int, int]:
    """(flops, bytes) of one round: ``segments`` lists (probes, objects) per
    bucket.  Only pairs within a segment count."""
    flops = sum(FLOPS_PER_PAIR * m * n for m, n in segments)
    probes = sum(m for m, _ in segments)
    objects = sum(n for _, n in segments)
    per_probe = COORD_BYTES + OUT_BYTES + (THR_BYTES if per_probe_threshold else 0)
    return flops, probes * per_probe + objects * COORD_BYTES


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline time of ``flops`` and ``nbytes`` on a chip, and which
    of the two bounds it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def load_peaks(device_kind: str, path=PEAKS) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
