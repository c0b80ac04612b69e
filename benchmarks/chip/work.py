"""Operations and bytes that one prefill or decode step needs, from the
model's shapes, as its family counts them (``prefill_flops``,
``decode_flops`` and ``decode_bytes`` of ``families/<family>.py``).

The counts are of the work itself, whatever implements it, so that a
share of a peak computed from them cannot pass 100% however a later
kernel does the work.
"""
from __future__ import annotations

from typing import Any

from benchmarks.chip.weights import family_of


def prefill_flops(m: Any, batch: int, seq: int) -> float:
    """One prefill of ``batch`` prompts of ``seq`` tokens."""
    return family_of(m).prefill_flops(m, batch, seq)


def decode_flops(m: Any, batch: int, cache_len: int) -> float:
    """One decode step: one new token per row against ``cache_len``
    cached positions."""
    return family_of(m).decode_flops(m, batch, cache_len)


def decode_bytes(m: Any, batch: int, cache_len: int) -> float:
    """Least bytes one decode step moves to and from HBM."""
    return family_of(m).decode_bytes(m, batch, cache_len)
