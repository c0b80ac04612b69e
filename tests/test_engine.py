"""In-process serving engine + batcher on a reduced model (CPU)."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import Model
from repro.serving.batcher import Batcher, ServeRequest
from repro.serving.engine import Engine, EngineConfig
from repro.sharding.policy import ShardingPolicy


@pytest.fixture(scope="module")
def engine():
    arch = ARCHS["granite-3-2b"].reduced()
    m = Model(arch, ShardingPolicy(mesh=None), param_dtype=jnp.float32)
    params = m.init(jax.random.key(0))
    return arch, Engine(m, params, EngineConfig(max_batch=4, max_seq=64))


def test_generate_greedy_matches_stepwise(engine):
    """Engine generation equals manual prefill + argmax decode."""
    arch, eng = engine
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (2, 12), 0, arch.vocab_size), np.int32)
    out = eng.generate(prompts, max_new=5)
    logits, cache = eng.model.prefill(eng.params, jnp.asarray(prompts),
                                      max_seq=64)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    for i in range(5):
        assert np.array_equal(np.asarray(tok[:, 0]), out[:, i])
        if i < 4:
            logits, cache = eng.model.decode_step(
                eng.params, cache, jnp.int32(12 + i), tok)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]


def test_generate_splits_its_wall_time_into_host_and_sync(engine):
    """The engine's running totals: one generate per call, and host plus
    sync seconds make up each call's wall time."""
    arch, eng = engine
    prompts = np.zeros((3, 10), np.int32)
    eng.generate(prompts, max_new=4)            # compiles batch 3
    n0, host0, sync0 = eng.generates, eng.host_s, eng.sync_s
    wall = 0.0
    for _ in range(4):
        t0 = time.perf_counter()
        eng.generate(prompts, max_new=4)
        wall += time.perf_counter() - t0
    assert eng.generates - n0 == 4
    assert eng.sync_s > sync0 and eng.host_s > host0
    assert (eng.host_s - host0) + (eng.sync_s - sync0) == \
        pytest.approx(wall, rel=0.05)


def test_batcher_launches_on_full_batch(engine):
    arch, eng = engine
    clock = [0.0]
    b = Batcher(eng, timeout_ms=1e9, max_new=3, clock=lambda: clock[0])
    for i in range(4):
        b.submit(ServeRequest(i, np.arange(5, dtype=np.int32) + i,
                              deadline_s=10.0, submitted_s=0.0))
    done = b.pump()
    assert len(done) == 4
    assert all(r.result is not None and r.result.shape == (3,)
               for r in done)


def test_batcher_timeout_partial_launch(engine):
    arch, eng = engine
    clock = [0.0]
    b = Batcher(eng, timeout_ms=50.0, max_new=2, clock=lambda: clock[0])
    b.submit(ServeRequest(0, np.arange(4, dtype=np.int32),
                          deadline_s=10.0, submitted_s=0.0))
    assert b.pump() == []          # not full, not timed out
    clock[0] = 0.2                 # 200 ms later
    done = b.pump()
    assert len(done) == 1


def test_batcher_drops_past_deadline(engine):
    arch, eng = engine
    clock = [5.0]
    b = Batcher(eng, timeout_ms=10.0, clock=lambda: clock[0])
    b.submit(ServeRequest(0, np.arange(4, dtype=np.int32),
                          deadline_s=1.0, submitted_s=0.0))
    assert b.pump() == []
    assert b.dropped == 1
