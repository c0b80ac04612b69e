"""Pallas TPU int8×int8→int32 matmul with per-row / per-channel scales.

This is the compute path behind the *quantized model variants* — one of the
paper's accuracy-scaling axes (§2 "Model variants ... techniques like
quantization").  An int8 variant of a task trades ~0.3-1% accuracy for 2×
weight-memory and up to 2× MXU throughput (int8 ops run at 2× bf16 rate on
v5e), which is exactly the latency/accuracy/cost knob the MILP optimizes.

Tiling: grid ``(M/bm, N/bn, K/bk)`` with K innermost accumulating int32 in
VMEM scratch; the dequant epilogue (row scale × col scale) runs once at the
final K step.  Default blocks 256×256×512: ≤ 0.5 MiB int8 inputs + 256 KiB
int32 accumulator per step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU; Mosaic refuses an i32 x i32 dot
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


def quant_matmul_pallas(
    x_q: jax.Array,      # [M, K] int8
    w_q: jax.Array,      # [K, N] int8
    x_scale: jax.Array,  # [M] fp32
    w_scale: jax.Array,  # [N] fp32
    *,
    out_dtype=jnp.float32,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = x_q.shape
    N = w_q.shape[1]

    def fit(block, dim):
        b = min(block, dim)
        while dim % b:
            b //= 2
        return b

    bm, bn, bk = fit(block_m, M), fit(block_n, N), fit(block_k, K)
    n_k = K // bk

    kernel = functools.partial(_qmm_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            # scales as [M,1] / [1,N] columns and rows: 1-D blocks do not
            # match XLA's tiled layout of a long f32 vector
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, x_scale.astype(jnp.float32).reshape(M, 1),
      w_scale.astype(jnp.float32).reshape(1, N))
