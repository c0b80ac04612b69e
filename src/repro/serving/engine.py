"""In-process serving engine: jit'd prefill/decode with a KV-cache pool.

This is the datapath a *model instance* runs on its TPU segment.  The
simulator uses profiled latencies for cluster-scale runs; this engine is
the real thing for small models on local devices (examples + tests run it
on CPU) and is what ``serve_step`` lowering targets in the dry-run.

``generate`` runs each program with the greedy pick of the next token
folded in (``Engine.picking``), and launches decode step i+1 before it
pulls token i to the host, so the device always has the next step queued.
Each engine keeps running totals of where ``generate`` spends its wall
time: ``host_s`` (dispatching prefill and decode) and ``sync_s`` (blocked
pulling each token to the host, while the next step runs), with profiler
annotations ``engine.prefill|<arch>|<b>``, ``engine.decode|<arch>|<b>``
and ``engine.sync|<arch>`` over the same phases; and of its decode steps:
``steps_ahead`` (launched before the token they follow was pulled) and
``steps_past_eos`` (launched after every row had ended, thrown away).
``compile_totals`` counts the process's XLA compiles, persistent-cache
loads included, from one ``jax.monitoring`` listener installed when this
module is imported.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

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


def _pick(logits: jax.Array) -> jax.Array:
    """The greedy next token of each row: int32[B, 1]."""
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


class Engine:
    """Static-batch greedy serving engine for one model instance.

    ``prefill(params, tokens)`` and ``decode(params, cache, cache_len,
    tokens)`` are the model's jitted programs; each returns ``(logits,
    cache)``.  ``generate`` runs them through ``picking``, so whatever
    program sits in either attribute is the one served."""

    def __init__(self, model: Model, params, cfg: EngineConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        mesh = model.policy.mesh

        def prefill(params, tokens):
            return model.prefill(params, tokens, max_seq=cfg.max_seq)

        def decode(params, cache, cache_len, tokens):
            return model.decode_step(params, cache, cache_len, tokens)

        self._jit_kw: Dict[str, Dict[str, Any]] = {
            "prefill": {}, "decode": {"donate_argnums": (1,)}}
        if mesh is not None:
            from jax.sharding import NamedSharding
            pspec = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 model.param_specs())
            self._jit_kw["prefill"]["in_shardings"] = (pspec, None)
        self.prefill = jax.jit(prefill, **self._jit_kw["prefill"])
        self.decode = jax.jit(decode, **self._jit_kw["decode"])
        self._picked: Dict[Tuple[str, Any], Any] = {}

        # running totals of generate's wall time and steps (see the
        # module doc)
        self.generates = 0
        self.host_s = 0.0
        self.sync_s = 0.0
        self.steps_ahead = 0
        self.steps_past_eos = 0

    # ------------------------------------------------------------------
    def picking(self, name: str):
        """The program in attribute ``name`` (``prefill`` or ``decode``)
        with the greedy pick folded in: one jitted program, still named
        ``name``, that returns ``(cache, next token int32[B, 1])``.  Built
        once per program object, so a replaced program is served as it
        is."""
        fn = getattr(self, name)
        prog = self._picked.get((name, fn))
        if prog is None:
            def picked(*args):
                logits, cache = fn(*args)
                return cache, _pick(logits)
            picked.__name__ = name        # the module stays jit_<name>
            prog = jax.jit(picked, **self._jit_kw[name])
            self._picked[(name, fn)] = prog
        return prog

    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """Batched greedy decode. prompts: [B, S] int32 (right-aligned,
        same length — the batcher pads).  Returns [B, max_new]: after a
        row's ``eos_id`` the row reads ``eos_id``, and once every row has
        ended the columns left read 0."""
        t0 = time.perf_counter()
        out, sync = self._generate(prompts, max_new, eos_id)
        # _generate's frame, and the device arrays it held, are gone
        self.host_s += time.perf_counter() - t0 - sync
        self.sync_s += sync
        self.generates += 1
        return out

    def _generate(self, prompts: np.ndarray, max_new: int,
                  eos_id: Optional[int]) -> Tuple[np.ndarray, float]:
        """The token loop: ``(tokens, seconds blocked on pulls)``.  Decode
        step i+1 is launched from token i while that token is still on the
        device, and only then is token i pulled; ``max_new - 1`` decode
        steps run unless every row ends first."""
        B, S = prompts.shape
        assert B <= self.cfg.max_batch and S < self.cfg.max_seq
        arch = self.model.arch.name
        prefill, decode = self.picking("prefill"), self.picking("decode")
        with jax.profiler.TraceAnnotation(f"engine.prefill|{arch}|{B}"):
            cache, tok = prefill(self.params, prompts)
        out = np.zeros((B, max_new), np.int32)
        done = np.zeros((B,), bool)
        sync = 0.0
        for i in range(max_new):
            head = tok
            ahead = i < max_new - 1
            if ahead:
                with jax.profiler.TraceAnnotation(
                        f"engine.decode|{arch}|{B}"):
                    cache, tok = decode(self.params, cache,
                                        np.int32(S + i), head)
                self.steps_ahead += 1
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"engine.sync|{arch}"):
                col = np.asarray(head)[:, 0]
            sync += time.perf_counter() - ts
            out[:, i] = np.where(done, eos_id or 0, col)
            if eos_id is not None:
                done |= col == eos_id
                if done.all():
                    self.steps_past_eos += ahead
                    break
        return out, sync
