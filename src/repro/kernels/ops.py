"""jit'd dispatch wrappers for the Pallas kernels.

The kernels target TPU and lower to Mosaic there.  On the CPU backend
(tests) they run in Pallas interpret mode; any other backend is an error,
so a run on the wrong device fails instead of silently interpreting.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels target TPU; backend {backend!r} "
                       "has neither Mosaic nor the CPU interpreter path")


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 512, block_kv: int = 512):
    """[B,Sq,H,hd] × [B,Skv,KV,hd]² → [B,Sq,H,hd] (GQA-aware)."""
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_kv=block_kv,
                                  interpret=_interpret_default())


@partial(jax.jit, static_argnames=("block_kv",))
def decode_attention(q, k_cache, v_cache, cache_len, *, block_kv: int = 1024):
    """[B,1,H,hd] vs caches [B,S,KV,hd] → [B,1,H,hd]."""
    return decode_attention_pallas(q, k_cache, v_cache, cache_len,
                                   block_kv=block_kv,
                                   interpret=_interpret_default())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             init_state: Optional[jax.Array] = None):
    """Chunked SSD. Returns (y [B,S,nh,hd], final_state [B,nh,hd,ds])."""
    return ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                           init_state=init_state,
                           interpret=_interpret_default())


@partial(jax.jit, static_argnames=("out_dtype",))
def quant_matmul(x_q, w_q, x_scale, w_scale, *, out_dtype=jnp.float32):
    """int8 [M,K] × int8 [K,N] → out_dtype [M,N] with row/col scales."""
    return quant_matmul_pallas(x_q, w_q, x_scale, w_scale,
                               out_dtype=out_dtype,
                               interpret=_interpret_default())


def quantize_int8(x, axis: int = -1):
    return _ref.quantize_int8(x, axis)


def quant_linear(x: jax.Array, w_q: jax.Array, w_scale: jax.Array
                 ) -> jax.Array:
    """Dynamic-activation-quant linear: quantize x per-row on the fly and
    run the int8 kernel. x: [..., K]; w_q: [K, N] int8."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    x_q, x_scale = _ref.quantize_int8(x2, axis=-1)
    out = quant_matmul(x_q, w_q, x_scale, w_scale, out_dtype=jnp.float32)
    return out.reshape(shape[:-1] + (w_q.shape[1],)).astype(x.dtype)
