"""Pure-jnp oracle for the cross-match join.

Semantics (probabilistic spatial join on the unit sphere):
given catalog ``bucket`` (N,3) and probe set ``probes`` (M,3), both unit
vectors, and a cosine threshold ``cos_thr`` = cos(match radius):

  best_idx[m] = argmax_n <probes[m], bucket[n]>       (nearest neighbour)
  best_dot[m] = the corresponding max dot product
  n_cand[m]   = #{n : <probes[m], bucket[n]> >= cos_thr}

A probe 'matches' iff n_cand > 0 (equivalently best_dot >= cos_thr).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["crossmatch_ref", "crossmatch_fused_ref", "crossmatch_shared_ref"]


def _dots(probes: jnp.ndarray, bucket: jnp.ndarray) -> jnp.ndarray:
    """(M, N) f32 dots.  ``HIGHEST``: a TPU's default single bf16 pass is
    ~1e-3 off, far wider than a 1e-3 rad radius's 1 - cos ~ 5e-7."""
    return jnp.dot(probes, bucket.T, precision=jax.lax.Precision.HIGHEST)


def crossmatch_ref(bucket: jnp.ndarray, probes: jnp.ndarray, cos_thr: float):
    dots = _dots(probes, bucket)
    best_idx = jnp.argmax(dots, axis=1).astype(jnp.int32)
    best_dot = jnp.max(dots, axis=1)
    n_cand = jnp.sum(dots >= cos_thr, axis=1).astype(jnp.int32)
    return best_idx, best_dot, n_cand


def crossmatch_fused_ref(
    bucket: jnp.ndarray,
    probes: jnp.ndarray,
    bucket_seg: jnp.ndarray,
    probe_seg: jnp.ndarray,
    cos_thr: float,
):
    """Segmented oracle: probe m only considers bucket rows with
    ``bucket_seg == probe_seg[m]``; other pairs get dot -2 (below any real
    dot and any threshold).  ``best_idx`` indexes the concatenated bucket."""
    dots = _dots(probes, bucket)
    same = probe_seg[:, None] == bucket_seg[None, :]
    dots = jnp.where(same, dots, jnp.float32(-2.0))
    best_idx = jnp.argmax(dots, axis=1).astype(jnp.int32)
    best_dot = jnp.max(dots, axis=1)
    n_cand = jnp.sum(dots >= cos_thr, axis=1).astype(jnp.int32)
    return best_idx, best_dot, n_cand


def crossmatch_shared_ref(
    bucket: jnp.ndarray,
    probes: jnp.ndarray,
    bucket_seg: jnp.ndarray,
    probe_seg: jnp.ndarray,
    probe_thr: jnp.ndarray,
):
    """Shared-plan oracle: the fused segment mask *plus* a per-probe-row
    threshold vector, realizing the (queries x objects) predicate mask.

    Each probe row belongs to one query; ``probe_thr[m]`` is that query's
    own cos(match radius), so heterogeneous per-query predicates evaluate
    in the same masked pass instead of one device dispatch per predicate
    class.  Thresholds must lie in (-2, 1] (real cosines do); masked and
    padded pairs sit at dot -2 and can never pass one.
    """
    dots = _dots(probes, bucket)
    same = probe_seg[:, None] == bucket_seg[None, :]
    dots = jnp.where(same, dots, jnp.float32(-2.0))
    best_idx = jnp.argmax(dots, axis=1).astype(jnp.int32)
    best_dot = jnp.max(dots, axis=1)
    n_cand = jnp.sum(dots >= probe_thr[:, None], axis=1).astype(jnp.int32)
    return best_idx, best_dot, n_cand
