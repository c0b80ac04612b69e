"""Execution backends: HOW a dispatched batch gets served.

The :class:`~repro.runtime.cluster.ClusterRuntime` owns queues, batching,
early-drop and the event clock; a backend only answers "how long does THIS
server take to serve THIS batch?" plus optional capacity-change hooks.
Two implementations:

* :class:`SimBackend` — the profiled-latency lognormal model extracted
  from the legacy ``Simulator`` (p95 latency × lognormal jitter; the tail
  models stragglers).
* :class:`EngineBackend` — drives real :class:`repro.serving.engine.Engine`
  instances (reduced float32 archs on the CPU, or published widths in
  bf16 on a TPU) and uses the measured wall-clock generation time as the
  service time, so the same control loop and scenarios exercise the
  actual jit'd datapath.
"""
from __future__ import annotations

import os
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import (Any, Dict, List, Mapping, Optional, Protocol, Sequence,
                    Tuple, TYPE_CHECKING, runtime_checkable)

import numpy as np

if TYPE_CHECKING:   # pragma: no cover — typing only, avoids jax at import
    from repro.core.milp import PlanConfig
    from repro.core.taskgraph import TaskGraph
    from repro.runtime.cluster import Server


@runtime_checkable
class ExecutionBackend(Protocol):
    """Data-plane contract consumed by :class:`ClusterRuntime`.

    ``bind`` is called once per served app before the event loop starts
    — a single-app runtime calls it once with that app's graph/config, a
    multi-app runtime (``ClusterRuntime.multi``) once per co-located
    app.  Backends that key state by graph should store it under
    ``Server.app`` (every ``service_s`` call carries the owning app on
    its server); see :class:`EngineBackend` for the pattern.
    """

    def bind(self, graph: "TaskGraph", config: "PlanConfig",
             app: str = "") -> None:
        """Called once per app before serving starts (build engines,
        caches...).  ``app`` is the co-located app's tag ("" single-app)."""
        ...

    def service_s(self, server: "Server", batch: Sequence[Any],
                  now_s: float, rng: np.random.Generator) -> float:
        """Service time (seconds) for ``server`` executing ``batch``."""
        ...

    def on_capacity_change(self, servers: List["Server"]) -> None:
        """Called after failure-injection / elasticity changed the fleet."""
        ...


# ---------------------------------------------------------------------------
@dataclass
class SimBackend:
    """Profiled-latency model: lognormal jitter around the profiled p95.

    Draw-for-draw identical to the legacy ``Simulator`` service-time model
    so the compatibility shim stays seed-deterministic."""
    jitter_sigma: float = 0.08
    mu: float = -0.15

    def bind(self, graph, config, app=""):
        pass

    def service_s(self, server, batch, now_s, rng):
        return (server.tup.latency_ms / 1e3
                * float(rng.lognormal(self.mu, self.jitter_sigma)))

    def on_capacity_change(self, servers):
        pass


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    is changed here.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    a fixed path, so every run from this checkout finds what earlier runs
    compiled.  Call it at the start of a program, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
@dataclass
class EngineBackend:
    """Serve batches on real ``serving.Engine`` instances.

    Each arch is served as its ``reduced()`` float32 variant (the
    small-config CPU parity path), or, with ``full_width``, as published
    in bf16 (the chip path).  ``bind`` builds one engine per distinct arch
    the plan deploys: parameters are drawn inside one jit, and a warm-up
    generate at ``prompt_len`` compiles every batch size from 1 to
    ``max_batch``, so no compile lands inside a timed ``service_s``.
    Service time is the measured wall-clock of the batched greedy decode,
    scaled by ``time_scale`` (sim-seconds per wall-second); a batch over
    ``max_batch`` runs as several launches, and their times add up.
    ``engine_phases``, ``engine_steps`` and ``compile_totals`` hand the
    engines' running totals to whoever publishes them (the gateway, at
    scrape time).

    ``pool_time_scale`` maps a ClusterSpec pool name to ITS scale so a
    heterogeneous CPU parity run reflects relative device speeds (e.g.
    a MIG 2g slice of an A100 is not a v5e rectangle): a server's pool
    picks its own scale, pools absent from the map fall back to
    ``time_scale``.
    """
    max_batch: int = 4
    max_seq: int = 64
    prompt_len: int = 8
    max_new: int = 4
    time_scale: float = 1.0
    pool_time_scale: Optional[Mapping[str, float]] = None
    full_width: bool = False
    _engines: Dict[str, Any] = field(default_factory=dict, repr=False)
    # one graph per bound app ("" = single-app); engines are shared
    # across apps by arch — co-located apps reuse the same jit'd engine
    _graphs: Dict[str, Any] = field(default_factory=dict, repr=False)

    def bind(self, graph, config, app=""):
        self._graphs[app] = graph
        for tup, _ in config.instances():
            self.engine_for(graph.tasks[tup.task].variant(tup.variant).arch)

    # ------------------------------------------------------------------
    def engine_for(self, arch_name: str):
        """The warmed engine serving ``arch_name`` (built on first use)."""
        eng = self._engines.get(arch_name)
        if eng is None:
            import jax
            import jax.numpy as jnp
            from repro.configs import ARCHS
            from repro.models import Model
            from repro.serving.engine import Engine, EngineConfig
            from repro.sharding.policy import ShardingPolicy

            if self.full_width:
                arch, dtype = ARCHS[arch_name], jnp.bfloat16
            else:
                arch, dtype = ARCHS[arch_name].reduced(), jnp.float32
            model = Model(arch, ShardingPolicy(mesh=None),
                          param_dtype=dtype)
            # stable per-arch seed (str hash is salted per process)
            seed = zlib.crc32(arch_name.encode()) & 0x7FFFFFFF
            # one program: the float32 draws are its temporaries, which XLA
            # frees as it casts them, and the init costs one dispatch
            params = jax.jit(model.init)(jax.random.key(seed))
            eng = Engine(model, params,
                         EngineConfig(max_batch=self.max_batch,
                                      max_seq=self.max_seq))
            for b in range(1, self.max_batch + 1):
                eng.generate(np.zeros((b, self.prompt_len), np.int32),
                             max_new=2)
            self._engines[arch_name] = eng
        return eng

    def scale_for(self, pool: str) -> float:
        """The time scale of one pool (``time_scale`` if unmapped)."""
        if self.pool_time_scale is not None and pool in self.pool_time_scale:
            return float(self.pool_time_scale[pool])
        return self.time_scale

    def service_s(self, server, batch, now_s, rng):
        graph = self._graphs[getattr(server, "app", "")]
        task = graph.tasks[server.tup.task]
        arch_name = task.variant(server.tup.variant).arch
        eng = self.engine_for(arch_name)
        vocab = eng.model.arch.vocab_size
        # a fixed-shape engine serves a big batch in several launches;
        # each one runs, and the service time is their summed wall time
        n, cap = max(len(batch), 1), eng.cfg.max_batch
        wall = 0.0
        for lo in range(0, n, cap):
            prompts = np.asarray(
                rng.integers(0, vocab, size=(min(cap, n - lo),
                                             self.prompt_len)), np.int32)
            t0 = time.monotonic()
            eng.generate(prompts, max_new=self.max_new)
            wall += time.monotonic() - t0
        return wall * self.scale_for(server.tup.pool)

    def engine_phases(self) -> Dict[str, Tuple[int, float, float]]:
        """Per served arch: its engine's generate calls, and their host
        and sync seconds (``Engine.host_s`` / ``Engine.sync_s``)."""
        return {a: (e.generates, e.host_s, e.sync_s)
                for a, e in self._engines.items()}

    def engine_steps(self) -> Dict[str, Tuple[int, int]]:
        """Per served arch: its engine's decode steps launched before the
        token they follow was pulled, and those launched past every row's
        end and thrown away (``Engine.steps_ahead`` /
        ``Engine.steps_past_eos``)."""
        return {a: (e.steps_ahead, e.steps_past_eos)
                for a, e in self._engines.items()}

    def compile_totals(self) -> Tuple[int, float]:
        """The process's XLA compiles and their seconds, persistent-cache
        loads included (``serving.engine.compile_totals``)."""
        from repro.serving.engine import compile_totals
        return compile_totals()

    def on_capacity_change(self, servers):
        pass
