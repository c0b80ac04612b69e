"""In-process serving engine + batcher on a reduced model (CPU)."""
import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import Model
from repro.serving.batcher import Batcher, ServeRequest
from repro.serving.engine import Engine, EngineConfig, compile_totals
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


def _stepwise(eng, prompts, max_new, eos_id=None):
    """The reference: the public programs one step at a time, with the
    argmax on the host and each token pulled before the next step."""
    B, S = prompts.shape
    logits, cache = eng.prefill(eng.params, jnp.asarray(prompts))
    out = np.zeros((B, max_new), np.int32)
    done = np.zeros((B,), bool)
    for i in range(max_new):
        col = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        out[:, i] = np.where(done, eos_id or 0, col)
        if eos_id is not None:
            done |= col == eos_id
            if done.all():
                break
        if i < max_new - 1:
            logits, cache = eng.decode(eng.params, cache, jnp.int32(S + i),
                                       jnp.asarray(col[:, None]))
    return out


def _prompts(arch, batch, seed=1, length=12):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (batch, length), 0, arch.vocab_size), np.int32)


@pytest.mark.parametrize("max_new", [1, 2, 5])
@pytest.mark.parametrize("batch", [1, 2, 3, 4])
def test_generate_matches_the_stepwise_reference(engine, batch, max_new):
    """Every batch size up to max_batch, every length: the pipelined loop
    serves the tokens the stepwise reference picks, and launches exactly
    max_new - 1 decode steps, each before the token it follows is
    pulled."""
    arch, eng = engine
    prompts = _prompts(arch, batch, seed=batch)
    ahead0, past0 = eng.steps_ahead, eng.steps_past_eos
    out = eng.generate(prompts, max_new=max_new)
    assert eng.steps_ahead - ahead0 == max_new - 1
    assert eng.steps_past_eos == past0
    assert np.array_equal(out, _stepwise(eng, prompts, max_new))


def _rows_ending_apart(eng, arch, max_new, all_end):
    """An eos token and prompts whose rows reach it at different steps
    before the last (and, unless ``all_end``, one row that never does),
    found among the greedy continuations of 16 prompts."""
    pool = _prompts(arch, 16, seed=7)
    outs = np.concatenate([eng.generate(pool[i:i + 4], max_new=max_new)
                           for i in range(0, 16, 4)])
    for t in np.unique(outs[:, :max_new - 1]):
        first = {}
        for r in range(16):
            hit = np.flatnonzero(outs[r, :max_new - 1] == t)
            if hit.size:
                first.setdefault(int(hit[0]), r)
        never = [r for r in range(16) if t not in outs[r]]
        if len(first) >= 2 and (all_end or never):
            rows = list(first.values())[:3] + ([] if all_end else never[:1])
            return int(t), pool[rows]
    pytest.fail("no token ends two rows at different steps")


@pytest.mark.parametrize("all_end", [True, False],
                         ids=["every_row_ends", "one_row_runs_on"])
def test_generate_with_eos_matches_the_stepwise_reference(engine, all_end):
    """Rows that reach ``eos_id`` at different steps: the outputs are the
    reference's; once every row has ended, the one step launched ahead of
    that pull is thrown away, and otherwise none is."""
    arch, eng = engine
    max_new = 6
    eos, prompts = _rows_ending_apart(eng, arch, max_new, all_end)
    want = _stepwise(eng, prompts, max_new, eos_id=eos)
    ends = [np.flatnonzero(row == eos) for row in want]
    assert len({int(e[0]) for e in ends if e.size}) >= 2
    assert all(e.size for e in ends) == all_end
    past0 = eng.steps_past_eos
    out = eng.generate(prompts, max_new=max_new, eos_id=eos)
    assert np.array_equal(out, want)
    assert eng.steps_past_eos - past0 == int(all_end)


def test_a_longer_generate_compiles_nothing_after_the_warm_up(engine):
    """The backend warms every batch size at max_new=2; no op of a longer
    generate, with or without eos, depends on max_new."""
    arch, eng = engine
    prompts = _prompts(arch, 3, length=8)
    eng.generate(prompts, max_new=2)
    n0, _ = compile_totals()
    eng.generate(prompts, max_new=8)
    eng.generate(prompts, max_new=8, eos_id=int(prompts[0, 0]))
    assert compile_totals()[0] == n0


def test_the_served_steps_are_the_prefill_and_decode_programs(engine):
    """One execution per step, under a module named for its program, and
    a program put in ``decode``'s place is the one served."""
    arch, eng = engine
    prompts = _prompts(arch, 2)
    cache = eng.prefill(eng.params, jnp.asarray(prompts))[1]
    tok = jnp.zeros((2, 1), jnp.int32)
    assert "@jit_prefill" in eng.picking("prefill").lower(
        eng.params, prompts).as_text()
    assert "@jit_decode" in eng.picking("decode").lower(
        eng.params, cache, np.int32(12), tok).as_text()
    decode = eng.decode

    def negated(params, cache, cache_len, tokens):
        logits, cache = decode(params, cache, cache_len, tokens)
        return -logits, cache

    eng.decode = negated
    try:
        out = eng.generate(prompts, max_new=3)
    finally:
        eng.decode = decode
    logits = eng.prefill(eng.params, jnp.asarray(prompts))[0]
    assert np.array_equal(out[:, 0], np.argmax(logits[:, -1], -1))
    assert not np.array_equal(out, eng.generate(prompts, max_new=3))


def test_generate_splits_its_wall_time_into_host_and_sync(engine):
    """The engine's running totals: one generate per call, and host
    (dispatch) plus sync (blocked on the token pulls, overlapping the next
    step's run) make up each call's wall time.  The cyclic collector is
    paused around the calls: Python runs a collection at the next
    eval-breaker check after an allocation crosses its threshold, so one
    triggered inside ``generate`` can run after it returns, outside its
    clock, and a second-generation pass takes about a millisecond."""
    arch, eng = engine
    prompts = np.zeros((3, 10), np.int32)
    eng.generate(prompts, max_new=4)            # compiles batch 3
    n0, host0, sync0 = eng.generates, eng.host_s, eng.sync_s
    wall = 0.0
    gc.collect()
    gc.disable()
    try:
        for _ in range(4):
            t0 = time.perf_counter()
            eng.generate(prompts, max_new=4)
            wall += time.perf_counter() - t0
    finally:
        gc.enable()
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
