"""Pallas TPU kernel for the Mamba2 chunked SSD scan. [arXiv:2405.21060]

TPU adaptation: the SSD *dual form* turns the recurrence into per-chunk
dense matmuls (MXU work) plus a tiny cross-chunk state update, which maps
onto a grid ``(B, nh, n_chunks)`` with the chunk axis innermost
("arbitrary") carrying the running state ``[hd, ds]`` in VMEM scratch.

Per-step VMEM working set (q=128 chunk, hd=64, ds=128):

    x tile      q × hd × 4B  =  32 KiB        B/C tiles  2 × q × ds × 4B = 128 KiB
    L matrix    q × q  × 4B  =  64 KiB        state      hd × ds × 4B    =  32 KiB

≈ 0.3 MiB — the kernel is compute-dense (three q×q / q×hd / hd×ds matmul
chains per step) rather than bandwidth-bound, which is exactly why the
dual form beats the sequential scan on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, s0_ref,
                y_ref, sf_ref, state_ref, *, q: int, n_chunks: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0]

    x = x_ref[0, 0].astype(jnp.float32)           # [q, hd]
    A = a_ref[h]                                  # scalar decay rate (<0)
    # dt arrives twice, as a column [q,1] and a row [1,q], so that both
    # orientations of the chunk's cumulative decay come from masked
    # reductions instead of a cumsum or an in-kernel transpose
    dt_c = dtc_ref[0, 0].astype(jnp.float32)      # [q, 1]
    dt_r = dtr_ref[0, 0, 0].astype(jnp.float32)   # [1, q]
    Bm = b_ref[0].astype(jnp.float32)             # [q, ds]
    Cm = c_ref[0].astype(jnp.float32)             # [q, ds]

    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = iota_i >= iota_j
    dA_c = dt_c * A                               # [q, 1] (<= 0)
    dA_r = dt_r * A                               # [1, q]
    cs_c = jnp.sum(jnp.where(causal, jnp.broadcast_to(dA_r, (q, q)), 0.0),
                   axis=1, keepdims=True)         # [q, 1] inclusive cumsum
    cs_r = jnp.sum(jnp.where(iota_i <= iota_j,
                             jnp.broadcast_to(dA_c, (q, q)), 0.0),
                   axis=0, keepdims=True)         # [1, q]
    total = jnp.sum(dA_r, axis=1, keepdims=True)  # [1, 1] = cs[q-1]

    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i >= j  (mask the
    # exponent, not the output — masked diffs are positive and overflow)
    L = jnp.exp(jnp.where(causal, cs_c - cs_r, -1e30))
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [q,q]
    xdt = x * dt_c
    y = jax.lax.dot_general(L * scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # [q,hd]

    # entering-state contribution: y += (C · state^T) * exp(cs)
    state = state_ref[...]                        # [hd, ds]
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # [q,hd]
    y = y + y_off * jnp.exp(cs_c)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state' = state * exp(cs_last) + Σ_j decay_j dt_j x_j ⊗ B_j
    decay_states = jnp.exp(total - cs_c)          # [q, 1]
    wx = x * (decay_states * dt_c)                # [q, hd]
    new_contrib = jax.lax.dot_general(wx, Bm, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    state_ref[...] = state * jnp.exp(total) + new_contrib

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        sf_ref[0, 0] = state_ref[...]


def ssd_scan_pallas(
    x: jax.Array,     # [B, S, nh, hd] fp32
    dt: jax.Array,    # [B, S, nh] fp32 (already softplus'd)
    A: jax.Array,     # [nh] fp32 (negative)
    Bm: jax.Array,    # [B, S, ds]
    Cm: jax.Array,    # [B, S, ds]
    *,
    chunk: int = 128,
    init_state: Optional[jax.Array] = None,   # [B, nh, hd, ds]
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, nh, hd = x.shape
    ds = Bm.shape[-1]
    q = min(chunk, S)
    while S % q:
        q -= 1
    n_chunks = S // q

    xr = x.transpose(0, 2, 1, 3)                  # [B, nh, S, hd]
    dtc = dt.transpose(0, 2, 1)[..., None]        # [B, nh, S, 1]
    dtr = dt.transpose(0, 2, 1).reshape(B, nh, n_chunks, 1, q)
    s0 = (init_state if init_state is not None
          else jnp.zeros((B, nh, hd, ds), jnp.float32))

    kernel = functools.partial(_ssd_kernel, q=q, n_chunks=n_chunks)
    y, final = pl.pallas_call(
        kernel,
        grid=(B, nh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, q, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, q, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtc, dtr, A.astype(jnp.float32), Bm, Cm, s0)
    return y.transpose(0, 2, 1, 3), final
