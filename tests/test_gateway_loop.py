"""Where the live gateway's time goes (DESIGN.md §14): the event loop's
state seconds, the hop spans that end at release, the engine's phase
totals and compile counter as ``/metrics`` exposes them, and the
profiler-clock anchor that lays the tracer's spans over a JAX trace."""
import asyncio
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.milp import Planner
from repro.gateway import AsyncGateway
from repro.gateway.core import LOOP_STATES
from repro.models import Model
from repro.obs import Instrumentation, Tracer, parse_exposition
from repro.runtime.backend import SimBackend
from repro.serving.engine import Engine, EngineConfig, compile_totals
from repro.sharding.policy import ShardingPolicy

APP = "traffic_analysis"
BLOCK_S = 0.04


@pytest.fixture(scope="module")
def planned(traffic_profiler):
    g, prof = traffic_profiler
    cfg = Planner(g, prof, s_avail=64, max_tuples_per_task=32, bb_nodes=4,
                  bb_time_s=1.0).plan(10.0)
    assert cfg is not None
    return {APP: (g, cfg)}


class Blocking:
    """A backend whose ``service_s`` blocks the loop a fixed time, as an
    engine's generate does, and reports that time as the service."""

    def __init__(self, block_s=BLOCK_S):
        self.block_s = block_s
        self.blocked_s = 0.0
        self.calls = 0

    def bind(self, graph, config, app=""):
        pass

    def service_s(self, server, batch, now_s, rng):
        t0 = time.monotonic()
        time.sleep(self.block_s)
        self.blocked_s += time.monotonic() - t0
        self.calls += 1
        return self.block_s

    def on_capacity_change(self, servers):
        pass


class WithEngines(Blocking):
    """... that also keeps engine phases, step counts and compile
    counts."""

    def engine_phases(self):
        return {"arch-a": (self.calls, 0.25 * self.calls, 0.5)}

    def engine_steps(self):
        return {"arch-a": (7 * self.calls, 1)}

    def compile_totals(self):
        return 3, 1.5


def _serve(planned, backend, hooks, n_roots=12, gap_s=0.03, idle_s=0.15,
           during=None):
    """Serve ``n_roots`` roots, then idle; returns the gateway, the roots,
    the scrape taken at the end, and the wall seconds from just before
    ``start()`` to just after the scrape."""
    async def drive():
        gw = AsyncGateway(planned, backend, seed=0, hooks=hooks,
                          staleness_ms=1e4)
        t0 = time.monotonic()
        await gw.start()
        try:
            if during is not None:
                during(gw)
            roots = []
            for _ in range(n_roots):
                roots.append(await gw.submit(APP))
                await asyncio.sleep(gap_s)
            await asyncio.wait_for(
                asyncio.gather(*(r.done.wait() for r in roots)), 30.0)
            await asyncio.sleep(idle_s)
            text = hooks.registry.render()
            elapsed = time.monotonic() - t0
        finally:
            await gw.stop()
        return gw, roots, parse_exposition(text), elapsed

    return asyncio.run(drive())


def _loop_seconds(scrape):
    fam = scrape["jigsaw_gateway_loop_seconds_total"]
    return {s: fam[(("state", s),)] for s in LOOP_STATES}


def test_loop_states_sum_to_the_wall_time(planned):
    backend = Blocking()
    hooks = Instrumentation()
    _, roots, scrape, elapsed = _serve(planned, backend, hooks)
    assert all(r.outcome["status"] == "ok" for r in roots)
    secs = _loop_seconds(scrape)
    assert sum(secs.values()) == pytest.approx(elapsed, rel=0.02)
    assert secs["generate"] == pytest.approx(backend.blocked_s, rel=0.02)
    # a batch is released once its generate has returned, so replay holds
    # only the releases' own work; and the loop idled at the end
    assert 0 < secs["replay"] <= backend.calls * BLOCK_S
    assert secs["empty"] >= 0.1 and secs["batch_wait"] > 0


def test_a_gateway_hop_span_ends_at_its_release(planned):
    hooks = Instrumentation(tracer=Tracer())
    _, roots, _, _ = _serve(planned, Blocking(), hooks)
    n, spans = 0, []
    for gr in roots:
        events = []
        while not gr.events.empty():
            events.append(gr.events.get_nowait())
        hops = hooks.tracer.spans_for_root(gr.root_id, cat="hop")
        for ev in (e for e in events if e["event"] == "hop"):
            ends = [s.end_s for s in hops if s.name == ev["task"]]
            assert min(abs(t - ev["t"]) for t in ends) < 1e-3
            n += 1
        # the service span covers the generate, not a replay of it too,
        # and carries the generate's part in its args
        for s in hooks.tracer.spans_for_root(gr.root_id, cat="service"):
            assert s.args["generate_s"] == BLOCK_S
            spans.append(s.end_s - s.start_s)
    assert n >= len(roots)
    # a release waits at most for generates the loop ran first
    assert min(spans) >= BLOCK_S * 0.95
    assert np.median(spans) < 1.5 * BLOCK_S


class Slower(Blocking):
    """... that reports twice the time it blocked: a modelled device
    slower than the one the batch ran on."""

    def service_s(self, server, batch, now_s, rng):
        return 2 * super().service_s(server, batch, now_s, rng)


def _alone(planned, backend, hooks):
    """Three roots, each served alone: the next arrives after the last
    one finished, even with every generate slept out again."""
    return _serve(planned, backend, hooks, n_roots=3, gap_s=0.35)


def test_a_root_alone_takes_one_block_per_generate(planned):
    backend = Blocking()
    gw, roots, _, _ = _alone(planned, backend, Instrumentation())
    # detect waits its batch timeout, then both attrs queues wait theirs
    # and run back to back: every generate of a root is on its path once
    per_root = backend.calls / len(roots)
    assert per_root == 3
    t_attrs = min(gw._timeout[f"{APP}::{t}"]
                  for t in ("vehicle_attrs", "person_attrs"))
    expect_ms = (gw._timeout[f"{APP}::detect"] + t_attrs
                 + per_root * BLOCK_S * 1e3)
    for gr in roots:
        lat = gr.outcome["latency_ms"]
        assert expect_ms - 2.0 <= lat < expect_ms + BLOCK_S * 1e3


def test_replay_is_a_small_part_of_the_generates(planned):
    backend = Blocking()
    hooks = Instrumentation()
    _, roots, scrape, _ = _serve(planned, backend, hooks)
    assert all(r.outcome["status"] == "ok" for r in roots)
    assert _loop_seconds(scrape)["replay"] < 0.1 * backend.calls * BLOCK_S


def test_a_modelled_slower_device_is_slept_out(planned):
    hooks = Instrumentation(tracer=Tracer())
    _, roots, _, _ = _alone(planned, Slower(), hooks)
    spans = [s for gr in roots
             for s in hooks.tracer.spans_for_root(gr.root_id, cat="service")]
    assert spans
    for s in spans:
        assert s.args["generate_s"] == 2 * BLOCK_S
        assert 2 * BLOCK_S - 1e-6 <= s.end_s - s.start_s < 2.5 * BLOCK_S


def test_a_predicting_backend_is_released_after_its_service(planned):
    hooks = Instrumentation(tracer=Tracer())
    _, roots, _, _ = _serve(planned, SimBackend(), hooks)
    spans = [s for gr in roots
             for s in hooks.tracer.spans_for_root(gr.root_id, cat="service")]
    assert spans
    for s in spans:
        service = s.args["generate_s"]
        assert service - 1e-6 <= s.end_s - s.start_s < service + 0.01


def test_metrics_expose_the_backends_engine_phases_and_compiles(planned):
    backend = WithEngines()
    hooks = Instrumentation()
    _, _, scrape, _ = _serve(planned, backend, hooks, n_roots=3)
    assert scrape["jigsaw_engine_generates_total"] == {
        (("arch", "arch-a"),): backend.calls}
    sec = scrape["jigsaw_engine_seconds_total"]
    assert sec[(("arch", "arch-a"), ("phase", "host"))] == \
        pytest.approx(0.25 * backend.calls)
    assert sec[(("arch", "arch-a"), ("phase", "sync"))] == 0.5
    assert scrape["jigsaw_engine_steps_ahead_total"] == {
        (("arch", "arch-a"),): 7 * backend.calls}
    assert scrape["jigsaw_engine_steps_past_eos_total"] == {
        (("arch", "arch-a"),): 1}
    assert scrape["jigsaw_compiles_total"] == {(): 3}
    assert scrape["jigsaw_compile_seconds_total"] == {(): 1.5}


def test_a_backend_without_engines_publishes_the_loop_alone(planned):
    _, _, scrape, _ = _serve(planned, Blocking(), Instrumentation(),
                             n_roots=2)
    assert "jigsaw_gateway_loop_seconds_total" in scrape
    assert "jigsaw_engine_seconds_total" not in scrape
    assert "jigsaw_engine_steps_ahead_total" not in scrape
    assert "jigsaw_compiles_total" not in scrape


def test_the_compile_counter_counts_a_compile():
    n0, s0 = compile_totals()
    jax.jit(lambda x: x * 5 - 2)(np.arange(11.0)).block_until_ready()
    n1, s1 = compile_totals()
    assert n1 >= n0 + 1 and s1 > s0


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    start = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, start + int(e.start_ns))
                           for e in line.events)
    return out


def test_spans_land_on_the_profilers_clock(planned, tmp_path):
    """Under the profiler, the loop's ``gw.<state>`` and the engine's
    ``engine.*`` annotations are in the trace, and a tracer span placed
    by the exported anchor starts within 1 ms of an annotation opened at
    the same instant."""
    arch = ARCHS["granite-3-2b"].reduced()
    model = Model(arch, ShardingPolicy(mesh=None), param_dtype=jnp.float32)
    eng = Engine(model, model.init(jax.random.key(0)),
                 EngineConfig(max_batch=2, max_seq=32))
    prompts = np.zeros((2, 6), np.int32)
    eng.generate(prompts, max_new=3)          # compiles outside the trace
    hooks = Instrumentation(tracer=Tracer())
    probe = {}

    def mark(gw):
        with jax.profiler.TraceAnnotation("anchor-probe"):
            probe["t"] = gw.now()
        hooks.tracer.record("probe", "probe", probe["t"], probe["t"],
                            APP, -1)

    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(planned, Blocking(), hooks, n_roots=3, during=mark)
        eng.generate(prompts, max_new=3)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    names = {n for n, _ in events}
    assert {"gw.generate", "gw.replay", "gw.batch_wait",
            "gw.empty"} <= names
    assert {f"engine.prefill|{arch.name}|2", f"engine.decode|{arch.name}|2",
            f"engine.sync|{arch.name}"} <= names
    anchor = hooks.tracer.chrome_trace()["otherData"]
    assert anchor["clock"] == "CLOCK_REALTIME"
    placed_ns = anchor["anchor_ns"] + probe["t"] * anchor[
        "wall_per_unit"] * 1e9
    (annotated_ns,) = [t for n, t in events if n == "anchor-probe"]
    assert abs(placed_ns - annotated_ns) < 1e6
