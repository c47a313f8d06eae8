"""Kernel micro-benchmarks: wall us/call for the jnp reference paths on CPU
(relative comparisons) + analytic TPU-v5e time from flop/byte counts.

interpret-mode Pallas timings are NOT wall-clock meaningful (python
executes the kernel body); correctness is covered in tests/test_kernels.py.
"""
from __future__ import annotations

import numpy as np

from repro.kernels.crossmatch import ops as cm_ops
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.paged_attention.ops import dense_to_pages, paged_attention
from repro.launch.roofline import HW

from .common import emit, time_call


def crossmatch_bench(verbose=True):
    rng = np.random.default_rng(0)
    N, M = 10_000, 1_024
    b = rng.normal(size=(N, 3)).astype(np.float32)
    b /= np.linalg.norm(b, axis=1, keepdims=True)
    p = rng.normal(size=(M, 3)).astype(np.float32)
    p /= np.linalg.norm(p, axis=1, keepdims=True)
    thr = float(np.cos(0.01))
    us = time_call(lambda: cm_ops.crossmatch(b, p, thr, use_pallas=False)[0])
    flops = 2.0 * N * M * 3
    tpu_us = flops / HW.peak_flops * 1e6
    hbm_us = (N * 8 + M * 8) * 4 / HW.hbm_bw * 1e6  # padded coords bf16-ish
    if verbose:
        print(f"  crossmatch 10k x 1k: cpu={us:.0f}us  v5e compute~{tpu_us:.2f}us "
              f"hbm~{hbm_us:.2f}us (memory-bound: skipped off-segment tiles are the win)")
    emit("kernel_crossmatch", us, f"v5e_est_us={max(tpu_us, hbm_us):.2f}")


def grouped_matmul_bench(verbose=True):
    rng = np.random.default_rng(1)
    import jax.numpy as jnp

    sizes = jnp.array([512, 1024, 512, 2048])
    T, d, f = 4096, 1024, 1024
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, d, f)) * 0.02, jnp.float32)
    us = time_call(lambda: grouped_matmul(x, sizes, w, use_pallas=False))
    flops = 2.0 * T * d * f
    tpu_us = flops / HW.peak_flops * 1e6
    hbm_us = (T * d + 4 * d * f + T * f) * 2 / HW.hbm_bw * 1e6
    if verbose:
        print(f"  grouped_matmul 4kx1kx1k/4g: cpu={us:.0f}us  v5e compute~{tpu_us:.1f}us "
              f"hbm~{hbm_us:.1f}us")
    emit("kernel_grouped_matmul", us, f"v5e_est_us={max(tpu_us, hbm_us):.2f}")


def paged_attention_bench(verbose=True):
    rng = np.random.default_rng(2)
    import jax.numpy as jnp

    B, H, KV, D, page, P = 16, 16, 8, 128, 64, 32
    S = page * P
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32)
    kp, vp, pt = dense_to_pages(k, v, page)
    lens = jnp.full((B,), S, jnp.int32)
    us = time_call(lambda: paged_attention(q, kp, vp, pt, lens, use_pallas=False))
    bytes_moved = 2 * B * S * KV * D * 2  # K+V pages in bf16
    hbm_us = bytes_moved / HW.hbm_bw * 1e6
    flops = 4.0 * B * H * S * D
    tpu_us = flops / HW.peak_flops * 1e6
    if verbose:
        print(f"  paged_attention B16 S2048: cpu={us:.0f}us  v5e hbm~{hbm_us:.1f}us "
              f"compute~{tpu_us:.2f}us (bandwidth-bound as expected for decode)")
    emit("kernel_paged_attention", us, f"v5e_est_us={max(tpu_us, hbm_us):.2f}")


def run(verbose: bool = True):
    crossmatch_bench(verbose)
    grouped_matmul_bench(verbose)
    paged_attention_bench(verbose)


def main() -> None:
    run()


if __name__ == "__main__":
    main()
