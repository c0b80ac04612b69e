"""The precisions the plain references compute in: float32 at
``Precision.HIGHEST``, and the lower precisions of the control that the
comparison in :mod:`benchmarks.chip.correct` must fail."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
QUANTS = (None, "int8", "fp8")
_FP8_MAX = 448.0       # largest finite float8_e4m3fn


def check_quant(quant: Optional[str]) -> None:
    if quant not in QUANTS:
        raise ValueError(f"unknown quant {quant!r}; known {QUANTS}")


def fake_quant(x: jax.Array, axes: Sequence[int], quant: Optional[str]
               ) -> jax.Array:
    """``x`` rounded to ``quant`` with one symmetric scale per slice over
    ``axes`` (the contracted axes), returned in float32."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=tuple(axes), keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / _FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown quant {quant!r}; known {QUANTS}")


def mm(spec: str, x: jax.Array, w: jax.Array, x_axes: Sequence[int],
       w_axes: Sequence[int], quant: Optional[str]) -> jax.Array:
    """``einsum(spec, x, w)`` at ``HIGHEST``, both sides first rounded to
    ``quant`` over their contracted axes."""
    return jnp.einsum(spec, fake_quant(x, x_axes, quant),
                      fake_quant(w, w_axes, quant), precision=HI)
