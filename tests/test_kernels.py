"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp oracles (interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.crossmatch import ops as cm_ops
from repro.kernels.crossmatch.ref import crossmatch_ref
from repro.kernels.grouped_matmul.ops import grouped_matmul, hybrid_grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref, row_groups
from repro.kernels.paged_attention.ops import dense_to_pages, paged_attention


def _unit(n, seed):
    v = np.random.default_rng(seed).normal(size=(n, 3))
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)


# ------------------------------------------------------------------ crossmatch
class TestCrossmatch:
    @pytest.mark.parametrize("n,m", [(256, 128), (700, 300), (1024, 1), (33, 513)])
    @pytest.mark.parametrize("radius", [0.01, 0.1])
    def test_matches_ref(self, n, m, radius):
        bkt, prb = _unit(n, 1), _unit(m, 2)
        thr = float(np.cos(radius))
        ri, rd, rc = cm_ops.crossmatch(bkt, prb, thr, use_pallas=False)
        pi, pd, pc = cm_ops.crossmatch(bkt, prb, thr, use_pallas=True, bm=128, bn=256,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(rc), np.asarray(pc))
        np.testing.assert_allclose(np.asarray(rd), np.asarray(pd), rtol=1e-6)
        # argmax may tie; verify the dot of the chosen index is the max
        dots = np.asarray(prb) @ np.asarray(bkt).T
        np.testing.assert_allclose(
            dots[np.arange(m), np.asarray(pi)], dots.max(axis=1), rtol=1e-5
        )

    @pytest.mark.parametrize("bm,bn", [(128, 256), (128, 512), (256, 128)])
    def test_block_shape_sweep(self, bm, bn):
        bkt, prb = _unit(500, 3), _unit(200, 4)
        thr = float(np.cos(0.05))
        ri, rd, rc = cm_ops.crossmatch(bkt, prb, thr, use_pallas=False)
        pi, pd, pc = cm_ops.crossmatch(bkt, prb, thr, use_pallas=True, bm=bm, bn=bn,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(rc), np.asarray(pc))
        np.testing.assert_allclose(np.asarray(rd), np.asarray(pd), rtol=1e-6)

    def test_self_match(self):
        """Every point matches itself at any positive radius."""
        pts = _unit(300, 5)
        _, d, c = cm_ops.crossmatch(
            pts, pts, float(np.cos(0.01)), use_pallas=True, interpret=True
        )
        assert (np.asarray(c) >= 1).all()
        np.testing.assert_allclose(np.asarray(d), 1.0, atol=1e-5)

    def test_banded_near_diagonal(self):
        """With SFC-sorted identical sets, a moderate band keeps all matches."""
        from repro.core.sfc import htm_id

        pts = _unit(1024, 6)
        order = np.argsort(htm_id(pts, level=8), kind="stable")
        pts = pts[order]
        thr = float(np.cos(0.01))
        fi, fd, fc = cm_ops.crossmatch(
            pts, pts, thr, use_pallas=True, bm=128, bn=128, interpret=True
        )
        bi, bd, bc = cm_ops.crossmatch(
            pts, pts, thr, use_pallas=True, bm=128, bn=128, band=0, interpret=True
        )
        # band=0 keeps only the diagonal tile: self-match must survive
        np.testing.assert_allclose(np.asarray(bd), 1.0, atol=1e-5)
        assert (np.asarray(bc) >= 1).all()
        assert (np.asarray(bc) <= np.asarray(fc)).all()

    @given(st.integers(1, 400), st.integers(1, 400))
    @settings(max_examples=10, deadline=None)
    def test_property_any_shape(self, n, m):
        bkt, prb = _unit(n, n), _unit(m, m + 1)
        thr = float(np.cos(0.05))
        ri, rd, rc = cm_ops.crossmatch(bkt, prb, thr, use_pallas=False)
        pi, pd, pc = cm_ops.crossmatch(bkt, prb, thr, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(rc), np.asarray(pc))

    @pytest.mark.parametrize("radius", [1.7, 2.0, 3.0])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_padded_rows_not_counted_at_large_radius(self, radius, use_pallas):
        """Regression: cos_thr <= 0 used to count every zero-padded bucket
        row (dot 0 >= cos_thr) in n_cand.  The marker-column sentinel pins
        padded-row dots at -2, below any threshold."""
        bkt, prb = _unit(700, 7), _unit(300, 8)  # 700 % bn != 0 forces padding
        thr = float(np.cos(radius))
        assert thr <= 0.0
        ri, rd, rc = crossmatch_ref(jnp.asarray(bkt), jnp.asarray(prb), thr)
        _, d, c = cm_ops.crossmatch(
            bkt, prb, thr, use_pallas=use_pallas, bm=128, bn=256, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))
        np.testing.assert_allclose(np.asarray(d), np.asarray(rd), rtol=1e-6)

    def test_shape_bucketing_bounds_compiles(self):
        """Sweeping probe counts must reuse O(log M) compiled shapes."""
        bkt = _unit(500, 11)
        thr = float(np.cos(0.05))
        before = cm_ops.jit_cache_size()
        for m in (3, 5, 6, 7, 9, 13, 40, 41, 47, 100, 117):
            cm_ops.crossmatch(bkt, _unit(m, m), thr, use_pallas=False)
        grown = cm_ops.jit_cache_size() - before
        # 11 distinct sizes -> pow2 buckets {8, 16, 64, 128} -> <= 4 shapes
        assert 0 <= grown <= 4, grown


class TestCrossmatchFused:
    def _segments(self, sizes_b, sizes_p, seed=0):
        bkts = [_unit(n, seed + 10 + i) for i, n in enumerate(sizes_b)]
        prbs = [_unit(m, seed + 50 + i) for i, m in enumerate(sizes_p)]
        B, P = np.concatenate(bkts), np.concatenate(prbs)
        bseg = np.repeat(np.arange(len(sizes_b)), sizes_b)
        pseg = np.repeat(np.arange(len(sizes_p)), sizes_p)
        return bkts, prbs, B, P, bseg, pseg

    @pytest.mark.parametrize("use_pallas", [False, True])
    @pytest.mark.parametrize("radius", [0.05, 0.5])
    def test_matches_per_segment_oracle(self, use_pallas, radius):
        sizes_b, sizes_p = [100, 100, 57], [40, 1, 130]
        bkts, prbs, B, P, bseg, pseg = self._segments(sizes_b, sizes_p)
        thr = float(np.cos(radius))
        fi, fd, fc = cm_ops.crossmatch_fused(
            B, P, bseg, pseg, thr, use_pallas=use_pallas, bm=128, bn=128,
            interpret=True,
        )
        fi, fd, fc = map(np.asarray, (fi, fd, fc))
        off_b = np.cumsum([0] + sizes_b)
        off_p = np.cumsum([0] + sizes_p)
        for s in range(len(sizes_b)):
            ri, rd, rc = map(
                np.asarray,
                crossmatch_ref(jnp.asarray(bkts[s]), jnp.asarray(prbs[s]), thr),
            )
            sl = slice(off_p[s], off_p[s + 1])
            np.testing.assert_array_equal(fc[sl], rc)
            np.testing.assert_allclose(fd[sl], rd, rtol=1e-6)
            dots = prbs[s] @ bkts[s].T
            chosen = dots[np.arange(sizes_p[s]), fi[sl] - off_b[s]]
            np.testing.assert_allclose(chosen, dots.max(axis=1), rtol=1e-5)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_probe_segment_without_bucket_rows(self, use_pallas):
        """A probe whose segment has no bucket rows matches nothing."""
        B = _unit(64, 1)
        P = _unit(10, 2)
        bseg = np.zeros(64, np.int32)
        pseg = np.full(10, 3, np.int32)  # segment 3 has no bucket rows
        _, d, c = cm_ops.crossmatch_fused(
            B, P, bseg, pseg, float(np.cos(3.0)), use_pallas=use_pallas,
            bm=128, bn=128, interpret=True,
        )
        assert (np.asarray(c) == 0).all()
        assert (np.asarray(d) <= -1.5).all()  # masked sentinel, never a match


# ------------------------------------------------------------------ grouped matmul
class TestGroupedMatmul:
    @pytest.mark.parametrize(
        "sizes,d,f",
        [
            ([128, 256, 128, 512], 256, 192),
            ([128, 128], 512, 512),
            ([384, 128, 128, 128, 256], 128, 64),
        ],
    )
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, sizes, d, f, dtype):
        rng = np.random.default_rng(0)
        sizes = jnp.array(sizes)
        T, G = int(sizes.sum()), len(sizes)
        x = jnp.asarray(rng.normal(size=(T, d)), dtype)
        w = jnp.asarray(rng.normal(size=(G, d, f)) * 0.1, dtype)
        ref = grouped_matmul_ref(x.astype(jnp.float32), sizes, w.astype(jnp.float32))
        out = grouped_matmul(x, sizes, w, bt=128, bf=64, bk=128, use_pallas=True)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), rtol=tol, atol=tol
        )

    def test_block_sweep(self):
        rng = np.random.default_rng(1)
        sizes = jnp.array([256, 256, 512])
        x = jnp.asarray(rng.normal(size=(1024, 384)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, 384, 256)) * 0.1, jnp.float32)
        ref = grouped_matmul_ref(x, sizes, w)
        for bt, bf, bk in [(128, 128, 128), (256, 256, 384), (128, 64, 192)]:
            out = grouped_matmul(x, sizes, w, bt=bt, bf=bf, bk=bk, use_pallas=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5
            )

    def test_row_groups(self):
        g = row_groups(jnp.array([2, 3, 1]), 6)
        np.testing.assert_array_equal(np.asarray(g), [0, 0, 1, 1, 1, 2])

    def test_hybrid_paths_agree(self):
        """Indexed (gather) and scan (kernel) paths compute the same y."""
        rng = np.random.default_rng(2)
        sizes = jnp.array([128, 128, 256])
        x = jnp.asarray(rng.normal(size=(512, 128)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, 128, 64)) * 0.1, jnp.float32)
        ref = grouped_matmul_ref(x, sizes, w)
        hyb = hybrid_grouped_matmul(x, sizes, w, threshold_rows=129, bt=128, bf=64, bk=128)
        np.testing.assert_allclose(np.asarray(hyb), np.asarray(ref), rtol=1e-4)


# ------------------------------------------------------------------ paged attention
class TestPagedAttention:
    @pytest.mark.parametrize("h,kv", [(8, 8), (8, 4), (8, 1), (16, 2)])
    @pytest.mark.parametrize("page,pages", [(16, 4), (32, 2), (8, 16)])
    def test_matches_ref(self, h, kv, page, pages):
        rng = np.random.default_rng(0)
        B, D = 3, 32
        S = page * pages
        q = jnp.asarray(rng.normal(size=(B, h, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, kv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, kv, D)), jnp.float32)
        kp, vp, pt = dense_to_pages(k, v, page)
        lens = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
        ref = paged_attention(q, kp, vp, pt, lens, use_pallas=False)
        out = paged_attention(q, kp, vp, pt, lens, use_pallas=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        rng = np.random.default_rng(1)
        B, H, KV, D, page, P = 2, 8, 4, 64, 16, 4
        S = page * P
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.bfloat16)
        kp, vp, pt = dense_to_pages(k, v, page)
        lens = jnp.array([S, S // 2], jnp.int32)
        ref = paged_attention(q, kp, vp, pt, lens, use_pallas=False)
        out = paged_attention(q, kp, vp, pt, lens, use_pallas=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2,
        )

    def test_shared_pages_across_sequences(self):
        """Two sequences pointing at the SAME pages (prefix sharing — the
        bucket-contention case) attend identically."""
        rng = np.random.default_rng(2)
        B, H, KV, D, page, P = 2, 4, 4, 16, 8, 4
        q1 = jnp.asarray(rng.normal(size=(1, H, D)), jnp.float32)
        q = jnp.concatenate([q1, q1], axis=0)
        kp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, page, KV, D)), jnp.float32)
        pt = jnp.tile(jnp.arange(P, dtype=jnp.int32)[None], (B, 1))
        lens = jnp.array([page * P, page * P], jnp.int32)
        out = paged_attention(q, kp, vp, pt, lens, use_pallas=True)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[1]), rtol=1e-6)

    def test_length_masking(self):
        """Slots past seq_len must not contribute: perturbing them is a no-op."""
        rng = np.random.default_rng(3)
        B, H, KV, D, page, P = 1, 4, 2, 16, 8, 4
        S = page * P
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
        kp, vp, pt = dense_to_pages(k, v, page)
        lens = jnp.array([10], jnp.int32)
        out1 = paged_attention(q, kp, vp, pt, lens, use_pallas=True)
        kp2 = kp.at[2:].set(99.0)
        vp2 = vp.at[2:].set(-99.0)
        out2 = paged_attention(q, kp2, vp2, pt, lens, use_pallas=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)
