"""Pallas TPU kernel: blocked dot-threshold cross-match.

TPU-native adaptation of the paper's sorted merge-scan join (§3.1): on the
sphere, ``angdist(a,b) < eps  <=>  <u_a,u_b> > cos(eps)``, so the per-bucket
join is a (M,3)x(3,N) matmul + threshold — an MXU workload, not a
pointer-chase.  Both operands arrive HTM-sorted, so the match matrix is
band-limited; the optional ``band`` parameter skips tiles outside the band
(block-sparse matmul), which is the kernel-level analogue of the paper's
"only overlapping buckets are joined".

Layout: the coordinate axis is zero-padded to 8 so the K dimension of the
MXU matmul is tile-aligned; M and N are padded to block multiples by the
``ops`` wrapper.  Grid = (M/bm, N/bn) with the N dimension innermost and
"arbitrary" semantics: each probe-tile's outputs are revisited across
bucket tiles and accumulated with a running max / count.

Per-row operands and outputs are 2-D: a per-probe vector is an (M, 1)
column (block (bm, 1), sublane-major like the row reductions of the
(bm, bn) dots tile) and a per-bucket-row vector is a (1, N) row (block
(1, bn), lane-major like the tile's columns).  Mosaic refuses 1-D blocks
smaller than XLA's 1-D tiling of the whole array, which is 1024 elements
once the array has that many.

The dots use ``Precision.HIGHEST``: the default radius puts the threshold
at 1 - cos ~ 5e-7, below what a single bf16 MXU pass resolves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "crossmatch_pallas",
    "crossmatch_fused_pallas",
    "crossmatch_shared_pallas",
    "COORD_PAD",
    "PAD_SEG",
]

COORD_PAD = 8  # zero-padded coordinate dimension (MXU K alignment)
_NEG = -2.0  # dots lie in [-1, 1]
_BIG = 2**30
_HIGHEST = jax.lax.Precision.HIGHEST
PAD_SEG = float(2**20)  # segment id assigned to padded rows (sorts last,
#                         exactly representable in f32, matches no real seg)


def _probe_row_spec(bm):
    """Block of an (M, 1) per-probe column."""
    return pl.BlockSpec((bm, 1), lambda i, j: (i, 0))


def _bucket_row_spec(bn):
    """Block of a (1, N) per-bucket-row vector."""
    return pl.BlockSpec((1, bn), lambda i, j: (0, j))


def _row_out_shape(m):
    """(best_idx, best_dot, n_cand) as (M, 1) columns; best_idx indexes
    the (concatenated) bucket rows."""
    return [
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
    ]


def _dots(probe_ref, bucket_ref):
    """(bm, bn) f32 dots of one probe tile with one bucket tile."""
    return jax.lax.dot_general(
        probe_ref[...],
        bucket_ref[...],
        (((1,), (1,)), ((), ())),
        precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _accumulate(dots, j, bn, cos_thr, idx_ref, dot_ref, cnt_ref):
    """Fold one (bm, bn) tile of dots into the running max/argmin-id/count
    held in the (bm, 1) output blocks."""
    ids = jax.lax.broadcasted_iota(jnp.int32, dots.shape, 1) + j * bn
    tile_best = jnp.max(dots, axis=1, keepdims=True)
    is_best = dots >= tile_best
    tile_idx = jnp.min(
        jnp.where(is_best, ids, jnp.int32(_BIG)), axis=1, keepdims=True
    )
    tile_cnt = jnp.sum((dots >= cos_thr).astype(jnp.int32), axis=1, keepdims=True)

    run_best = dot_ref[...]
    improved = tile_best > run_best
    dot_ref[...] = jnp.where(improved, tile_best, run_best)
    idx_ref[...] = jnp.where(improved, tile_idx, idx_ref[...])
    cnt_ref[...] = cnt_ref[...] + tile_cnt


def _kernel(bucket_ref, probe_ref, idx_ref, dot_ref, cnt_ref, *, cos_thr, bn, band):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dot_ref[...] = jnp.full_like(dot_ref, jnp.float32(_NEG))
        idx_ref[...] = jnp.zeros_like(idx_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    def _body():
        _accumulate(
            _dots(probe_ref, bucket_ref), j, bn, cos_thr, idx_ref, dot_ref, cnt_ref
        )

    if band is None:
        _body()
    else:
        # Band-sparse: both inputs are SFC-sorted, so matches concentrate
        # near the (scaled) diagonal. Tiles outside the band are skipped
        # entirely — no load, no matmul.
        n_i = pl.num_programs(0)
        n_j = pl.num_programs(1)
        center = (i * n_j) // jnp.maximum(n_i, 1)
        pl.when(jnp.abs(j - center) <= band)(_body)


@functools.partial(jax.jit, static_argnames=("cos_thr", "bm", "bn", "band", "interpret"))
def crossmatch_pallas(
    bucket: jnp.ndarray,  # (N, COORD_PAD) f32, N % bn == 0
    probes: jnp.ndarray,  # (M, COORD_PAD) f32, M % bm == 0
    cos_thr: float,
    bm: int = 128,
    bn: int = 512,
    band: int | None = None,
    *,
    interpret: bool,
):
    m, kp = probes.shape
    n, kb = bucket.shape
    assert kp == COORD_PAD and kb == COORD_PAD, (kp, kb)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    grid = (m // bm, n // bn)
    kern = functools.partial(_kernel, cos_thr=cos_thr, bn=bn, band=band)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, COORD_PAD), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, COORD_PAD), lambda i, j: (i, 0)),
        ],
        out_specs=[_probe_row_spec(bm)] * 3,
        out_shape=_row_out_shape(m),
        interpret=interpret,
    )(bucket, probes)
    return out


def _fused_kernel(
    bucket_ref, probe_ref, bseg_ref, pseg_ref, idx_ref, dot_ref, cnt_ref,
    *, cos_thr, bn
):
    """Segmented (multi-bucket) cross-match tile.

    Probe row m may only match bucket rows whose segment id equals
    ``pseg[m]`` — the grouped_matmul trick applied to the join: k buckets'
    payloads and probe queues are concatenated segment-by-segment and
    evaluated in ONE device call, amortizing dispatch the way the paper
    amortizes disk reads across queries.  Both inputs arrive sorted by
    segment, so the valid region is block-diagonal; tiles whose segment
    ranges don't overlap are skipped entirely.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dot_ref[...] = jnp.full_like(dot_ref, jnp.float32(_NEG))
        idx_ref[...] = jnp.zeros_like(idx_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    ps = pseg_ref[...]  # (bm, 1) f32 segment ids, ascending
    bs = bseg_ref[...]  # (1, bn) f32 segment ids, ascending

    def _body():
        dots = jnp.where(ps == bs, _dots(probe_ref, bucket_ref), jnp.float32(_NEG))
        _accumulate(dots, j, bn, cos_thr, idx_ref, dot_ref, cnt_ref)

    overlap = (jnp.min(bs) <= jnp.max(ps)) & (jnp.max(bs) >= jnp.min(ps))
    pl.when(overlap)(_body)


def _shared_kernel(
    bucket_ref, probe_ref, bseg_ref, pseg_ref, thr_ref, idx_ref, dot_ref, cnt_ref,
    *, bn
):
    """Shared-plan tile: the fused segment mask plus per-probe thresholds.

    The query axis is fused into the kernel: each probe row carries its own
    query's cos threshold in ``thr_ref``, so a batch of queries with
    heterogeneous predicates — which the static-``cos_thr`` kernels would
    split into one dispatch (and one compile) per predicate class — runs as
    ONE masked device call.  The (queries x objects) predicate mask is the
    segment mask composed with the per-row threshold compare inside
    ``_accumulate``.  Same block-diagonal tile skip as the fused kernel.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dot_ref[...] = jnp.full_like(dot_ref, jnp.float32(_NEG))
        idx_ref[...] = jnp.zeros_like(idx_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    ps = pseg_ref[...]  # (bm, 1) f32 segment ids, ascending
    bs = bseg_ref[...]  # (1, bn) f32 segment ids, ascending

    def _body():
        dots = jnp.where(ps == bs, _dots(probe_ref, bucket_ref), jnp.float32(_NEG))
        # The (bm, 1) per-row thresholds broadcast against the dots tile.
        _accumulate(dots, j, bn, thr_ref[...], idx_ref, dot_ref, cnt_ref)

    overlap = (jnp.min(bs) <= jnp.max(ps)) & (jnp.max(bs) >= jnp.min(ps))
    pl.when(overlap)(_body)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def crossmatch_shared_pallas(
    bucket: jnp.ndarray,  # (N, COORD_PAD) f32, N % bn == 0, seg-sorted
    probes: jnp.ndarray,  # (M, COORD_PAD) f32, M % bm == 0, seg-sorted
    bucket_seg: jnp.ndarray,  # (1, N) f32 segment id per bucket row
    probe_seg: jnp.ndarray,  # (M, 1) f32 segment id per probe row
    probe_thr: jnp.ndarray,  # (M, 1) f32 per-probe cos threshold (traced!)
    bm: int = 128,
    bn: int = 512,
    *,
    interpret: bool,
):
    m, kp = probes.shape
    n, kb = bucket.shape
    assert kp == COORD_PAD and kb == COORD_PAD, (kp, kb)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    grid = (m // bm, n // bn)
    kern = functools.partial(_shared_kernel, bn=bn)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, COORD_PAD), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, COORD_PAD), lambda i, j: (i, 0)),
            _bucket_row_spec(bn),
            _probe_row_spec(bm),
            _probe_row_spec(bm),
        ],
        out_specs=[_probe_row_spec(bm)] * 3,
        out_shape=_row_out_shape(m),
        interpret=interpret,
    )(bucket, probes, bucket_seg, probe_seg, probe_thr)
    return out


@functools.partial(jax.jit, static_argnames=("cos_thr", "bm", "bn", "interpret"))
def crossmatch_fused_pallas(
    bucket: jnp.ndarray,  # (N, COORD_PAD) f32, N % bn == 0, seg-sorted
    probes: jnp.ndarray,  # (M, COORD_PAD) f32, M % bm == 0, seg-sorted
    bucket_seg: jnp.ndarray,  # (1, N) f32 segment id per bucket row
    probe_seg: jnp.ndarray,  # (M, 1) f32 segment id per probe row
    cos_thr: float,
    bm: int = 128,
    bn: int = 512,
    *,
    interpret: bool,
):
    m, kp = probes.shape
    n, kb = bucket.shape
    assert kp == COORD_PAD and kb == COORD_PAD, (kp, kb)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    grid = (m // bm, n // bn)
    kern = functools.partial(_fused_kernel, cos_thr=cos_thr, bn=bn)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, COORD_PAD), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, COORD_PAD), lambda i, j: (i, 0)),
            _bucket_row_spec(bn),
            _probe_row_spec(bm),
        ],
        out_specs=[_probe_row_spec(bm)] * 3,
        out_shape=_row_out_shape(m),
        interpret=interpret,
    )(bucket, probes, bucket_seg, probe_seg)
    return out
