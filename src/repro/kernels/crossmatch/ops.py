"""Jitted public wrappers for the cross-match kernels.

Handles padding and dispatch for two entry points:

``crossmatch``        — one bucket vs its probe batch.  Probe and bucket
                        counts are padded to the next power of two
                        (*shape bucketing*), so a query trace triggers
                        O(log max_M) jit compilations instead of one per
                        distinct batch size; ``jit_cache_size()`` exposes
                        the cores' compile count for benchmarks,
                        ``h2d_bytes()`` the bytes of the padded operands
                        handed to the jitted cores.
``crossmatch_fused``  — k buckets in ONE device call: payloads and probe
                        batches are concatenated with segment ids and the
                        join is evaluated as a segment-masked matmul
                        (grouped_matmul-style), amortizing dispatch the
                        way the paper amortizes disk reads.

Padded-row correctness: coordinates are zero-padded to ``COORD_PAD`` and a
*marker column* is used so padded bucket rows dot to exactly -2 with every
probe (probes carry 1.0 in the marker column, padded bucket rows -2.0,
real bucket rows 0.0).  -2 is below any real dot (unit vectors give
dots in [-1, 1]) and any threshold, so padded rows can never win the
argmax nor inflate ``n_cand`` — including when ``cos_thr <= 0`` (match
radius >= pi/2), which used to count every zero-padded row.  The fused
path gets the same guarantee from its segment mask (padded rows carry
segment ``PAD_SEG``, which matches no real segment).

Outputs come back as host numpy arrays: each wrapper fetches the core's
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

from .kernel import (
    COORD_PAD,
    PAD_SEG,
    crossmatch_fused_pallas,
    crossmatch_pallas,
    crossmatch_shared_pallas,
)
from .ref import crossmatch_fused_ref, crossmatch_ref, crossmatch_shared_ref

__all__ = [
    "crossmatch", "crossmatch_fused", "crossmatch_shared", "jit_cache_size",
    "h2d_bytes",
]

_PAD_THR = 2.0  # threshold for padded probe rows: above any dot, passes never

_MARKER_COL = 3  # first zero-padded coordinate column; see module docstring
_MIN_SHAPE = 8  # floor for power-of-two shape buckets

# Running total of the bytes of host-built operands passed to the jitted
# cores: each is transferred to the device on every call.
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
    """The ``interpret`` flag a core is traced with: as given, or from the
    backend when ``None`` (compiled on TPU, interpreted on CPU).  The jnp
    path ignores it, so it is pinned there to keep one compile entry."""
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
        f"the Pallas cross-match kernels run on tpu (compiled) or cpu "
        f"(interpreted), not on {backend!r}; use use_pallas=False"
    )


def _segmented_operands(bucket8, probes8, bucket_seg, probe_seg, bm, bn):
    """Block-pad both operands for the segmented kernels.  Padded rows get
    segment ``PAD_SEG``; segment ids become a (1, N) row and an (M, 1)
    column, the kernels' per-row layouts."""
    bucket_p = _pad_rows(bucket8, bn)
    probes_p = _pad_rows(probes8, bm)
    bseg = _pad_rows(bucket_seg[:, None], bn, PAD_SEG).reshape(1, -1)
    pseg = _pad_rows(probe_seg[:, None], bm, PAD_SEG)
    return bucket_p, probes_p, bseg, pseg


def _mark_probes(probes8: jnp.ndarray) -> jnp.ndarray:
    """Every probe row carries 1.0 in the marker column."""
    return probes8.at[:, _MARKER_COL].set(1.0)


def _sentinel_bucket_rows(bucket8: jnp.ndarray, n_real: int) -> jnp.ndarray:
    """Rows past ``n_real`` get -2.0 in the marker column: their dot with
    any (marked) probe is exactly -2, below every real dot and threshold."""
    if bucket8.shape[0] > n_real:
        bucket8 = bucket8.at[n_real:, _MARKER_COL].set(-2.0)
    return bucket8


def _host_prepare(bucket, probes):
    """Pow2-pad, COORD_PAD-widen, and marker/sentinel-mark both operands in
    host numpy — one array build + one transfer per operand at the jit
    boundary instead of a chain of eager device pads."""
    bucket = np.asarray(bucket, np.float32)
    probes = np.asarray(probes, np.float32)
    if bucket.shape[1] > _MARKER_COL or probes.shape[1] > _MARKER_COL:
        raise ValueError(
            f"coordinate width must be <= {_MARKER_COL}; column "
            f"{_MARKER_COL} is reserved for the padded-row marker"
        )
    n_true, m_true = bucket.shape[0], probes.shape[0]
    b8 = np.zeros((_pow2_ceil(n_true), COORD_PAD), np.float32)
    b8[:n_true, : bucket.shape[1]] = bucket
    b8[n_true:, _MARKER_COL] = -2.0
    p8 = np.zeros((_pow2_ceil(m_true), COORD_PAD), np.float32)
    p8[:m_true, : probes.shape[1]] = probes
    p8[:, _MARKER_COL] = 1.0
    return b8, p8, n_true, m_true


def _host_outputs(out, n_true: int, m_true: int):
    """Fetch a core's padded (best_idx, best_dot, n_cand) in one transfer
    and cut them to the ``m_true`` real probes in numpy; ``best_idx`` is
    clamped to the real bucket rows (padded rows cannot win, but
    belt-and-braces).  Eager device slices would compile once per
    distinct ``m_true``."""
    idx, dot, cnt = jax.device_get(out)
    return np.minimum(idx[:m_true], max(n_true - 1, 0)), dot[:m_true], cnt[:m_true]


@functools.partial(
    jax.jit, static_argnames=("cos_thr", "use_pallas", "bm", "bn", "band", "interpret")
)
def _crossmatch_jit(bucket8, probes8, cos_thr, use_pallas, bm, bn, band, interpret):
    """Inputs are already COORD_PAD wide, marker-marked, and pow2-padded;
    padded bucket rows dot to -2 with every probe on both paths."""
    m = probes8.shape[0]
    if not use_pallas:
        return crossmatch_ref(bucket8, probes8, cos_thr)
    n_in = bucket8.shape[0]
    bucket_p = _sentinel_bucket_rows(_pad_rows(bucket8, bn), n_in)
    probes_p = _mark_probes(_pad_rows(probes8, bm))
    idx, dot, cnt = crossmatch_pallas(
        bucket_p, probes_p, cos_thr, bm=bm, bn=bn, band=band, interpret=interpret
    )
    return idx[:m, 0], dot[:m, 0], cnt[:m, 0]


def crossmatch(
    bucket,
    probes,
    cos_thr: float,
    use_pallas: bool = False,
    bm: int = 128,
    bn: int = 512,
    band: int | None = None,
    interpret: bool | None = None,
):
    """Cross-match ``probes`` against ``bucket`` (both (?,3) unit vectors).

    Returns (best_idx, best_dot, n_cand) as host numpy arrays, each of
    length len(probes).
    ``use_pallas=False`` uses the jnp reference path;
    ``use_pallas=True`` runs the Pallas kernel, compiled on a TPU and
    interpreted on the CPU unless ``interpret`` says otherwise.

    Both operands are padded to the next power of two (in host numpy)
    before entering the jitted core, so the number of distinct compiled
    shapes over a whole trace is O(log2(max probe count)) rather than
    O(#batches).
    """
    interpret = _resolve_interpret(interpret, use_pallas)
    bucket8, probes8, n_true, m_true = _host_prepare(bucket, probes)
    _h2d[0] += bucket8.nbytes + probes8.nbytes
    out = _crossmatch_jit(
        bucket8, probes8, float(cos_thr), use_pallas, bm, bn, band, interpret
    )
    return _host_outputs(out, n_true, m_true)


def h2d_bytes() -> int:
    """Bytes of the padded operands handed to the jitted cores so far, in
    this process (the host-to-device traffic of the cross-match calls)."""
    return _h2d[0]


def jit_cache_size() -> int:
    """Total shapes compiled across the single-bucket, fused, and
    shared-plan cores (benchmarks gate this staying O(log max batch)).
    Counts the cores' cache entries only: eager device work outside them
    would compile without showing here (the wrappers run none)."""
    return int(
        _crossmatch_jit._cache_size()
        + _crossmatch_fused_jit._cache_size()
        + _crossmatch_shared_jit._cache_size()
    )


@functools.partial(
    jax.jit, static_argnames=("cos_thr", "use_pallas", "bm", "bn", "interpret")
)
def _crossmatch_fused_jit(
    bucket8, probes8, bucket_seg, probe_seg, cos_thr, use_pallas, bm, bn, interpret
):
    m = probes8.shape[0]
    if not use_pallas:
        return crossmatch_fused_ref(bucket8, probes8, bucket_seg, probe_seg, cos_thr)
    bucket_p, probes_p, bseg, pseg = _segmented_operands(
        bucket8, probes8, bucket_seg, probe_seg, bm, bn
    )
    idx, dot, cnt = crossmatch_fused_pallas(
        bucket_p, probes_p, bseg, pseg, cos_thr,
        bm=bm, bn=bn, interpret=interpret,
    )
    return idx[:m, 0], dot[:m, 0], cnt[:m, 0]


@functools.partial(jax.jit, static_argnames=("use_pallas", "bm", "bn", "interpret"))
def _crossmatch_shared_jit(
    bucket8, probes8, bucket_seg, probe_seg, probe_thr, use_pallas, bm, bn, interpret
):
    m = probes8.shape[0]
    if not use_pallas:
        return crossmatch_shared_ref(
            bucket8, probes8, bucket_seg, probe_seg, probe_thr
        )
    bucket_p, probes_p, bseg, pseg = _segmented_operands(
        bucket8, probes8, bucket_seg, probe_seg, bm, bn
    )
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
    """Shared-plan cross-match: the query axis fused into ONE device call.

    Like ``crossmatch_fused``, but the cos threshold is a *traced* per-probe
    array (``probe_thr[m]`` = probe m's owning query's cos(radius)) instead
    of a static scalar.  A batch of queries with K distinct match radii
    therefore costs one dispatch and at most one compile per pow2 shape
    pair — the static-threshold paths would pay K dispatches and K compile
    cache entries.  Thresholds must lie in (-2, 1]; real cosines do, and
    padded probe rows get ``_PAD_THR`` (+2, passes nothing).

    Returns (best_idx, best_dot, n_cand) as host numpy arrays of length
    len(probes); best_idx indexes the concatenated bucket array.
    """
    interpret = _resolve_interpret(interpret, use_pallas)
    bucket8, probes8, n_true, m_true = _host_prepare(bucket, probes)
    # Segment mask fences padded/real rows, exactly as in the fused path.
    bucket8[:, _MARKER_COL] = 0.0
    probes8[:, _MARKER_COL] = 0.0
    bseg = np.full(bucket8.shape[0], PAD_SEG, np.float32)
    bseg[:n_true] = np.asarray(bucket_seg, np.float32)
    pseg = np.full(probes8.shape[0], PAD_SEG, np.float32)
    pseg[:m_true] = np.asarray(probe_seg, np.float32)
    thr = np.full(probes8.shape[0], _PAD_THR, np.float32)
    thr[:m_true] = np.asarray(probe_thr, np.float32)
    _h2d[0] += (
        bucket8.nbytes + probes8.nbytes + bseg.nbytes + pseg.nbytes + thr.nbytes
    )
    out = _crossmatch_shared_jit(
        bucket8, probes8, jnp.asarray(bseg), jnp.asarray(pseg), jnp.asarray(thr),
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
    """Fused multi-bucket cross-match: ONE device call for k buckets.

    ``bucket``/``probes`` are the segment-sorted concatenations of the k
    bucket payloads / probe batches; ``bucket_seg``/``probe_seg`` give each
    row's segment (0..k-1).  A probe only matches bucket rows of its own
    segment; ``best_idx`` indexes the *concatenated* bucket array (callers
    subtract their segment's row offset).  A probe whose segment is empty
    gets n_cand == 0.

    Shapes are padded to powers of two (padded rows get segment
    ``PAD_SEG``), bounding compile count over a trace.

    Returns (best_idx, best_dot, n_cand) as host numpy arrays of length
    len(probes).
    """
    interpret = _resolve_interpret(interpret, use_pallas)
    bucket8, probes8, n_true, m_true = _host_prepare(bucket, probes)
    # The segment mask replaces the marker column: padded/real row fencing
    # comes from PAD_SEG, so neutralize the marker values set above.
    bucket8[:, _MARKER_COL] = 0.0
    probes8[:, _MARKER_COL] = 0.0
    bseg = np.full(bucket8.shape[0], PAD_SEG, np.float32)
    bseg[:n_true] = np.asarray(bucket_seg, np.float32)
    pseg = np.full(probes8.shape[0], PAD_SEG, np.float32)
    pseg[:m_true] = np.asarray(probe_seg, np.float32)
    _h2d[0] += bucket8.nbytes + probes8.nbytes + bseg.nbytes + pseg.nbytes
    out = _crossmatch_fused_jit(
        bucket8, probes8, jnp.asarray(bseg), jnp.asarray(pseg),
        float(cos_thr), use_pallas, bm, bn, interpret,
    )
    return _host_outputs(out, n_true, m_true)
