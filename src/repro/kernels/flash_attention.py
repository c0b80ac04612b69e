"""Pallas TPU flash attention (prefill / training, causal, GQA-aware).

Tiling
------
Grid is ``(B*H, Sq/bq, Skv/bkv)`` with the KV axis innermost ("arbitrary"
semantics — it carries the online-softmax state in VMEM scratch across
steps).  Per-step VMEM working set with the default blocks
(bq=512, bkv=512, hd≤256):

    q tile    bq × hd × 4B   ≤ 512 KiB
    k,v tiles 2 × bkv × hd × 4B ≤ 1 MiB
    scores    bq × bkv × 4B  = 1 MiB
    acc       bq × hd × 4B   ≤ 512 KiB

≈ 3 MiB — comfortably inside a v5e core's VMEM, and every matmul dim is a
multiple of 128 (MXU-aligned).  GQA is handled by the k/v index_map
(query-head → kv-head integer division), so KV tensors are never
materialized repeated.

Causal block skipping: KV blocks strictly above the diagonal are skipped
with ``pl.when`` (no MXU work), which halves prefill FLOPs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, bq: int, bkv: int,
                 n_kv_blocks: int, seq_q: int, seq_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions of this tile's rows/cols (prefill: q offset == kv offset)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) \
        + (seq_kv - seq_q)
    k_pos = kj * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = (kj * bkv) <= (qi * bq + bq - 1 + (seq_kv - seq_q))

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                  # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,               # [B, Sq, H, hd]
    k: jax.Array,               # [B, Skv, KV, hd]
    v: jax.Array,               # [B, Skv, KV, hd]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = scale if scale is not None else hd ** -0.5
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bkv = min(block_kv, Skv)
    while Skv % bkv:
        bkv //= 2
    n_q, n_kv = Sq // bq, Skv // bkv

    # [B,S,H,hd] -> [B*H, S, hd]; kv heads stay un-repeated
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv,
        n_kv_blocks=n_kv, seq_q=Sq, seq_kv=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j, g=g: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
