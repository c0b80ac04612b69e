"""The comparison that decides ``correct``.

After the window, a sample of the requests each model served, drawn from
the seed, is run through the plain float32 reference of the model's family
(``logits`` of ``families/<family>.py``) over its prompt and its served
tokens. For every served token the gap is how far the
reference's logit of that token lies below the reference's best at that
position. Greedy decoding in bf16 serves a token the reference ranks
second only where the two lie within the bf16 rounding of each other; a
wrong cache, position, mask or weight serves tokens far below the best.
Per model the sample gives three numbers (:func:`gap_numbers`): the
widest gap, the mean gap, and the share of served tokens the reference
does not rank first. A configuration names which of them it compares,
each with its limit.

The control (:func:`control_gaps`) is the reference in a lower precision
put in the program's place: at each position of the same prompts and
served tokens, the gap of the token that it puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import family_of

Call = Tuple[str, np.ndarray, np.ndarray]      # arch, prompts, served tokens


def sample_rows(calls: Sequence[Call], arch: str, seed: int, rows: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Up to ``rows`` served requests of ``arch`` (prompt, tokens), drawn
    without replacement from every request the window served. Every
    request of a cell has the same lengths, so each is as long as the
    longest."""
    prompts = [p for a, p, _ in calls if a == arch]
    served = [t for a, _, t in calls if a == arch]
    if not prompts:
        raise ValueError(f"{arch}: the window served nothing to compare")
    P, T = np.concatenate(prompts), np.concatenate(served)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    pick = np.sort(rng.choice(len(P), size=min(rows, len(P)),
                              replace=False))
    return P[pick], T[pick]


def reference_logits(m: Any, words: np.ndarray, prompts: np.ndarray,
                     served: np.ndarray, quant: Optional[str] = None
                     ) -> jax.Array:
    """Logits ``[B, N, V]`` at the positions that chose the ``N`` served
    tokens: the last prompt position and each served token but the last."""
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    return family_of(m).logits(m, words, tokens, prompts.shape[1] - 1,
                               quant)


@jax.jit
def _gaps(ref: jax.Array, chosen: jax.Array) -> jax.Array:
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]


def served_gaps(ref: jax.Array, served: np.ndarray) -> np.ndarray:
    """Gap below the reference's best of each served token."""
    return np.asarray(_gaps(ref, jnp.asarray(served, jnp.int32)))


def control_gaps(ref: jax.Array, ctl: jax.Array) -> np.ndarray:
    """Gap below the reference's best of the token the control ranks
    first."""
    return np.asarray(_gaps(ref, jnp.argmax(ctl, axis=-1)))


def gap_numbers(gaps: np.ndarray, arch: str) -> Dict[str, float]:
    """``max_gap.<arch>``, ``mean_gap.<arch>`` and ``miss_share.<arch>``
    of one sample's gaps."""
    return {f"max_gap.{arch}": float(gaps.max()),
            f"mean_gap.{arch}": float(gaps.mean()),
            f"miss_share.{arch}": float((gaps > 0).mean())}


def compare_calls(dims: Dict[str, Any], words: np.ndarray,
                  calls: List[Call], seed: int, rows: int,
                  prompt_len: int) -> Dict[str, float]:
    """The gap numbers of each model's sample."""
    out: Dict[str, float] = {}
    for arch in sorted({a for a, _, _ in calls}):
        prompts, served = sample_rows(calls, arch, seed, rows)
        if prompts.shape[1] != prompt_len:
            raise ValueError(f"{arch}: prompts of {prompts.shape[1]} tokens,"
                             f" the mix sends {prompt_len}")
        ref = reference_logits(dims[arch], words, prompts, served)
        out.update(gap_numbers(served_gaps(ref, served), arch))
    return out
