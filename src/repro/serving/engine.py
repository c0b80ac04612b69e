"""In-process serving engine: jit'd prefill/decode with a KV-cache pool.

This is the datapath a *model instance* runs on its TPU segment.  The
simulator uses profiled latencies for cluster-scale runs; this engine is
the real thing for small models on local devices (examples + tests run it
on CPU) and is what ``serve_step`` lowering targets in the dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.model import Model
from repro.sharding.policy import ShardingPolicy


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 512
    decode_budget: int = 64       # max new tokens per request


class Engine:
    """Continuous-batching serving engine for one model instance.

    ``prefill(params, tokens)`` and ``decode(params, cache, cache_len,
    tokens)`` are the jitted programs ``generate`` runs; each returns
    ``(logits, cache)``."""

    def __init__(self, model: Model, params, cfg: EngineConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        mesh = model.policy.mesh

        def prefill(params, tokens):
            return model.prefill(params, tokens, max_seq=cfg.max_seq)

        def decode(params, cache, cache_len, tokens):
            return model.decode_step(params, cache, cache_len, tokens)

        if mesh is not None:
            from jax.sharding import NamedSharding
            pspec = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 model.param_specs())
            self.prefill = jax.jit(prefill, in_shardings=(pspec, None))
            self.decode = jax.jit(decode, donate_argnums=(1,))
        else:
            self.prefill = jax.jit(prefill)
            self.decode = jax.jit(decode, donate_argnums=(1,))

        self.cache = None
        self.cache_len = 0
        self.active: List[Dict[str, Any]] = []

    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """Batched greedy decode. prompts: [B, S] int32 (right-aligned,
        same length — the batcher pads).  Returns [B, max_new]."""
        B, S = prompts.shape
        assert B <= self.cfg.max_batch and S < self.cfg.max_seq
        logits, cache = self.prefill(self.params, jnp.asarray(prompts))
        out = np.zeros((B, max_new), np.int32)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        done = np.zeros((B,), bool)
        for i in range(max_new):
            out[:, i] = np.where(done, eos_id or 0, np.asarray(tok[:, 0]))
            if eos_id is not None:
                done |= np.asarray(tok[:, 0]) == eos_id
                if done.all():
                    break
            if i == max_new - 1:
                break
            logits, cache = self.decode(self.params, cache,
                                         jnp.int32(S + i), tok)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return out
