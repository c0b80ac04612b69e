"""In-process serving engine: jit'd prefill/decode with a KV-cache pool.

This is the datapath a *model instance* runs on its TPU segment.  The
simulator uses profiled latencies for cluster-scale runs; this engine is
the real thing for small models on local devices (examples + tests run it
on CPU) and is what ``serve_step`` lowering targets in the dry-run.

Each engine keeps running totals of where ``generate`` spends its wall
time: ``host_s`` (dispatching prefill, decode and argmax) and ``sync_s``
(blocked pulling each token to the host), with profiler annotations
``engine.prefill|<arch>|<b>``, ``engine.decode|<arch>|<b>`` and
``engine.sync|<arch>`` over the same phases.  ``compile_totals`` counts
the process's XLA compiles, persistent-cache loads included, from one
``jax.monitoring`` listener installed when this module is imported.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.model import Model
from repro.sharding.policy import ShardingPolicy

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0, 0.0]            # process-wide: compiles, their seconds


def _on_compile(event: str, duration: float, **_: Any) -> None:
    if event == _COMPILE_EVENT:
        _compiles[0] += 1
        _compiles[1] += duration


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def compile_totals() -> Tuple[int, float]:
    """XLA compiles in this process since the module was imported, and
    their seconds; a persistent-cache load counts as one."""
    return _compiles[0], _compiles[1]


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
        # running totals of generate's wall time (see the module doc)
        self.generates = 0
        self.host_s = 0.0
        self.sync_s = 0.0

    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """Batched greedy decode. prompts: [B, S] int32 (right-aligned,
        same length — the batcher pads).  Returns [B, max_new]."""
        t0 = time.perf_counter()
        B, S = prompts.shape
        assert B <= self.cfg.max_batch and S < self.cfg.max_seq
        arch = self.model.arch.name
        sync = 0.0
        with jax.profiler.TraceAnnotation(f"engine.prefill|{arch}|{B}"):
            logits, cache = self.prefill(self.params, jnp.asarray(prompts))
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out = np.zeros((B, max_new), np.int32)
        done = np.zeros((B,), bool)
        for i in range(max_new):
            head = tok[:, 0]
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"engine.sync|{arch}"):
                col = np.asarray(head)
            sync += time.perf_counter() - ts
            out[:, i] = np.where(done, eos_id or 0, col)
            if eos_id is not None:
                done |= col == eos_id
                if done.all():
                    break
            if i == max_new - 1:
                break
            with jax.profiler.TraceAnnotation(f"engine.decode|{arch}|{B}"):
                logits, cache = self.decode(self.params, cache,
                                             jnp.int32(S + i), tok)
                tok = jnp.argmax(logits[:, -1],
                                 axis=-1).astype(jnp.int32)[:, None]
        self.generates += 1
        self.sync_s += sync
        self.host_s += time.perf_counter() - t0 - sync
        return out
