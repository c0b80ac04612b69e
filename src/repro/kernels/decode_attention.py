"""Pallas TPU flash-decode: one new token vs a long KV cache.

The KV sequence is blocked over the innermost grid axis; the online-softmax
carry (m, l, acc) lives in VMEM scratch.  The query tile is the GQA group
``[G, hd]`` (all query heads that share one kv head), so the kernel's matmul
shape is ``[G, hd] × [hd, bkv]`` — for G=8, hd=128, bkv=1024 that is one
MXU-aligned ``8×128×1024`` step per block.

``cache_len`` arrives in SMEM; blocks entirely past it are skipped with
``pl.when`` — a decode against a half-filled cache does half the work
(this is the straggler-mitigation property the serving simulator models).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bkv: int, n_kv: int):
    j = pl.program_id(1)
    cache_len = len_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bkv < cache_len)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale       # [G, hd]
        k = k_ref[0].astype(jnp.float32)               # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < cache_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,          # [B, 1, H, hd]
    k_cache: jax.Array,    # [B, S, KV, hd]
    v_cache: jax.Array,    # [B, S, KV, hd]
    cache_len: jax.Array,  # scalar int32
    *,
    scale: Optional[float] = None,
    block_kv: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    B, S, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    bkv = min(block_kv, S)
    while S % bkv:
        bkv //= 2
    n_kv = S // bkv

    qr = q[:, 0].reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    len_arr = jnp.asarray(cache_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=scale, bkv=bkv,
                               n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(len_arr, qr, kr, vr)
    return out.reshape(B, KV * G, hd)[:, None].reshape(B, 1, H, hd)
