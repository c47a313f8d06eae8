"""Jitted public wrappers for the cross-match kernel.

One jitted core, ``_crossmatch_shared_jit``, evaluates every join: k
buckets' payloads and probe batches concatenated with segment ids, each
probe row carrying its own query's cos threshold, in ONE device call.
Three entry points build its operands:

``crossmatch_shared`` — the general call: segment ids and per-probe
                        thresholds as given (the query axis fused into
                        one masked call).
``crossmatch_fused``  — k buckets under one threshold: the caller's
                        segments and a constant threshold column.
``crossmatch``        — one bucket vs its probe batch: one segment and a
                        constant threshold column.

Probe and bucket counts are padded to the next power of two (*shape
bucketing*), so a query trace triggers O(log max_M) jit compilations
instead of one per distinct batch size; ``jit_cache_size()`` exposes the
core's compile count for benchmarks, ``h2d_bytes()`` the bytes of the
padded operands handed to it.

Padded-row correctness: padded rows carry segment ``PAD_SEG``, which
matches no real segment, so the segment mask pins a padded bucket row's
dot with every probe at -2 — below any real dot (unit vectors give dots
in [-1, 1]) and any threshold — and it can never win the argmax nor
inflate ``n_cand``, whatever the radius.  Padded probe rows get threshold
``_PAD_THR`` (+2) and are cut off on the host.

Outputs come back as host numpy arrays: each call fetches the core's
three padded outputs in one ``jax.device_get`` (which waits for the
device) and cuts them to the real probe count on the host.  No device op
runs on the outputs, so a probe count not seen before compiles nothing
beyond its power-of-two core shape.

Pallas runs compiled on a TPU and in interpret mode on the CPU.  The
wrappers resolve ``interpret`` from the backend, outside ``jit``, when the
caller leaves it ``None``; any other backend has no Pallas path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import COORD_PAD, PAD_SEG, crossmatch_shared_pallas
from .ref import crossmatch_shared_ref

__all__ = [
    "crossmatch", "crossmatch_fused", "crossmatch_shared", "jit_cache_size",
    "h2d_bytes",
]

_PAD_THR = 2.0  # threshold for padded probe rows: above any dot, passes never
_COORDS = 3  # unit vectors on the sphere
_MIN_SHAPE = 8  # floor for power-of-two shape buckets

# Running total of the bytes of host-built operands passed to the jitted
# core: each is transferred to the device on every call.
_h2d = [0]


def _pow2_ceil(n: int, floor: int = _MIN_SHAPE) -> int:
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


def _pad_rows(x: jnp.ndarray, mult: int, fill: float = 0.0) -> jnp.ndarray:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=fill)
    return x


def _resolve_interpret(interpret: bool | None, use_pallas: bool) -> bool:
    """The ``interpret`` flag the core is traced with: as given, or from
    the backend when ``None`` (compiled on TPU, interpreted on CPU).  The
    jnp path ignores it, so it is pinned there to keep one compile entry."""
    if not use_pallas:
        return False
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas cross-match kernel runs on tpu (compiled) or cpu "
        f"(interpreted), not on {backend!r}; use use_pallas=False"
    )


def _pad_column(values, rows: int, n_true: int, fill: float) -> np.ndarray:
    """A length-``rows`` f32 vector: ``values`` then ``fill``."""
    out = np.full(rows, fill, np.float32)
    out[:n_true] = np.asarray(values, np.float32)
    return out


def _host_prepare(bucket, probes, bucket_seg, probe_seg, probe_thr):
    """Pow2-pad and COORD_PAD-widen both operands, and pad the segment ids
    and thresholds, in host numpy — one array build + one transfer per
    operand at the jit boundary instead of a chain of eager device pads.
    Returns the core's five operands and the real row counts."""
    bucket = np.asarray(bucket, np.float32)
    probes = np.asarray(probes, np.float32)
    if bucket.shape[1] > _COORDS or probes.shape[1] > _COORDS:
        raise ValueError(f"coordinate width must be <= {_COORDS}")
    n_true, m_true = bucket.shape[0], probes.shape[0]
    b8 = np.zeros((_pow2_ceil(n_true), COORD_PAD), np.float32)
    b8[:n_true, : bucket.shape[1]] = bucket
    p8 = np.zeros((_pow2_ceil(m_true), COORD_PAD), np.float32)
    p8[:m_true, : probes.shape[1]] = probes
    bseg = _pad_column(bucket_seg, b8.shape[0], n_true, PAD_SEG)
    pseg = _pad_column(probe_seg, p8.shape[0], m_true, PAD_SEG)
    thr = _pad_column(probe_thr, p8.shape[0], m_true, _PAD_THR)
    return (b8, p8, bseg, pseg, thr), n_true, m_true


def _host_outputs(out, n_true: int, m_true: int):
    """Fetch the core's padded (best_idx, best_dot, n_cand) in one transfer
    and cut them to the ``m_true`` real probes in numpy; ``best_idx`` is
    clamped to the real bucket rows (padded rows cannot win, but
    belt-and-braces).  Eager device slices would compile once per
    distinct ``m_true``."""
    idx, dot, cnt = jax.device_get(out)
    return np.minimum(idx[:m_true], max(n_true - 1, 0)), dot[:m_true], cnt[:m_true]


def h2d_bytes() -> int:
    """Bytes of the padded operands handed to the jitted core so far, in
    this process (the host-to-device traffic of the cross-match calls)."""
    return _h2d[0]


def jit_cache_size() -> int:
    """Shapes compiled by the one core (benchmarks gate this staying
    O(log max batch)).  Counts the core's cache entries only: eager
    device work outside it would compile without showing here (the
    wrappers run none)."""
    return int(_crossmatch_shared_jit._cache_size())


@functools.partial(jax.jit, static_argnames=("use_pallas", "bm", "bn", "interpret"))
def _crossmatch_shared_jit(
    bucket8, probes8, bucket_seg, probe_seg, probe_thr, use_pallas, bm, bn, interpret
):
    m = probes8.shape[0]
    if not use_pallas:
        return crossmatch_shared_ref(
            bucket8, probes8, bucket_seg, probe_seg, probe_thr
        )
    bucket_p = _pad_rows(bucket8, bn)
    probes_p = _pad_rows(probes8, bm)
    bseg = _pad_rows(bucket_seg[:, None], bn, PAD_SEG).reshape(1, -1)
    pseg = _pad_rows(probe_seg[:, None], bm, PAD_SEG)
    thr = _pad_rows(probe_thr[:, None], bm, _PAD_THR)
    idx, dot, cnt = crossmatch_shared_pallas(
        bucket_p, probes_p, bseg, pseg, thr,
        bm=bm, bn=bn, interpret=interpret,
    )
    return idx[:m, 0], dot[:m, 0], cnt[:m, 0]


def crossmatch_shared(
    bucket,
    probes,
    bucket_seg,
    probe_seg,
    probe_thr,
    use_pallas: bool = False,
    bm: int = 128,
    bn: int = 512,
    interpret: bool | None = None,
):
    """Segmented cross-match with per-probe thresholds, in ONE device call.

    ``bucket``/``probes`` are the segment-sorted concatenations of k
    bucket payloads / probe batches ((?,3) unit vectors);
    ``bucket_seg``/``probe_seg`` give each row's segment.  A probe only
    matches bucket rows of its own segment, and ``probe_thr[m]`` is probe
    m's owning query's cos(radius): a batch of queries with K distinct
    match radii costs one dispatch and at most one compile per pow2 shape
    pair.  Thresholds must lie in (-2, 1]; real cosines do.  A probe whose
    segment is empty gets n_cand == 0.

    ``use_pallas=False`` uses the jnp reference path; ``use_pallas=True``
    runs the Pallas kernel, compiled on a TPU and interpreted on the CPU
    unless ``interpret`` says otherwise.

    Returns (best_idx, best_dot, n_cand) as host numpy arrays of length
    len(probes); best_idx indexes the concatenated bucket array (callers
    subtract their segment's row offset).
    """
    interpret = _resolve_interpret(interpret, use_pallas)
    operands, n_true, m_true = _host_prepare(
        bucket, probes, bucket_seg, probe_seg, probe_thr
    )
    _h2d[0] += sum(a.nbytes for a in operands)
    b8, p8, bseg, pseg, thr = operands
    out = _crossmatch_shared_jit(
        b8, p8, jnp.asarray(bseg), jnp.asarray(pseg), jnp.asarray(thr),
        use_pallas, bm, bn, interpret,
    )
    return _host_outputs(out, n_true, m_true)


def crossmatch_fused(
    bucket,
    probes,
    bucket_seg,
    probe_seg,
    cos_thr: float,
    use_pallas: bool = False,
    bm: int = 128,
    bn: int = 512,
    interpret: bool | None = None,
):
    """``crossmatch_shared`` with one threshold for every probe."""
    thr = np.full(len(probes), cos_thr, np.float32)
    return crossmatch_shared(
        bucket, probes, bucket_seg, probe_seg, thr, use_pallas, bm, bn, interpret
    )


def crossmatch(
    bucket,
    probes,
    cos_thr: float,
    use_pallas: bool = False,
    bm: int = 128,
    bn: int = 512,
    interpret: bool | None = None,
):
    """Cross-match ``probes`` against one ``bucket`` under one threshold:
    ``crossmatch_fused`` with a single segment."""
    return crossmatch_fused(
        bucket, probes, np.zeros(len(bucket), np.float32),
        np.zeros(len(probes), np.float32), cos_thr, use_pallas, bm, bn, interpret,
    )
