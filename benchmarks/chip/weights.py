"""Random weights drawn from the run's seed, shared by every model family.

The benchmark, not the program, makes the weights: the served engine is
given them in the type it serves (bfloat16), and the plain reference draws
the same values again, layer by layer, without taking anything the
program holds. Every leaf of every layer has a key of its own, folded from
the seed, the leaf's index and the layer, so one layer can be drawn alone
and equals the same layer of the stacked draw. Each family
(``families/<family>.py``) keeps its own index of leaf names and its own
tree; the seed's words, the key fold and the draw are the same for all.
"""
from __future__ import annotations

import sys
from types import ModuleType
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SERVED_DTYPE = jnp.bfloat16


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words: traced, so one
    compiled program serves every seed."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def leaf_key(words: jax.Array, index: int) -> jax.Array:
    """The key of the leaf with a family's fixed ``index``: it never
    depends on which other leaves a model has."""
    root = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return jax.random.fold_in(root, index)


def draw(key: jax.Array, shape: Tuple[int, ...], scale: float,
         dtype: Any = SERVED_DTYPE) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def family_of(dims: Any) -> ModuleType:
    """The family module whose ``Dims`` made ``dims``: its weights, its
    reference and its work counts."""
    return sys.modules[type(dims).__module__]
