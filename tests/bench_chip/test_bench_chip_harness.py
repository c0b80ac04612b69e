"""CPU rehearsal of the chip benchmark: its phases at a tiny size, steered
from the test, through the repo's planner, HTTP gateway, backend and bf16
engine.

- files dropped in by name become a cell and a metric;
- a root's latency runs from its due time, not its send;
- a whole run compares what it served and comes out ``correct``, and each
  fault of the timed path that a serving cell can have makes it false;
- ``run.py`` exits non-zero without a TPU, and in a checkout that holds
  only the benchmark's own files.
"""
import asyncio
import functools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import traffic_gen as TG  # noqa: E402

CELL = "tiny.quick"
SEED = 2 ** 31 + 4242
WINDOW_S = 3.0
# the tiny model's limit: bf16 serving reads gaps of 0.001-0.003 here,
# the fp8 control 0.03-0.06
TINY_LIMIT = 0.01

# a metric a later change could drop in: how many roots were due
ROOTS_DUE = '''"""Client: roots due in the window."""


def read(ctx, part):
    return float(len(ctx.window.roots))
'''


def tiny_checkout(root: Path) -> Path:
    """A checkout whose benchmark has one more configuration, mix and
    metric, added as files and entries only."""
    home = root / "benchmarks" / "chip"
    shutil.copytree(REPO / "benchmarks" / "chip", home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((home / "configs" / "ta-granite.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=512,
               attention_bias=True, served_as="tiny-dense", knee_rps=20.0,
               limits={"max_gap.tiny-dense": TINY_LIMIT})
    cfg["engine"] = {"full_width": True, "max_batch": 4, "max_seq": 32}
    (home / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((home / "traffic" / "steady.json").read_text())
    tr.update(prompt_len=8, max_new=4, rate_of_knee=1.0, drain_s=5.0,
              trace_s=1.0)
    (home / "traffic" / "quick.json").write_text(json.dumps(tr))
    (home / "metrics" / "roots_due.py").write_text(ROOTS_DUE)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "quick", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "roots_due", "unit": "req",
                               "better": "higher", "source": "host_clock",
                               "layer": "client", "moves": "latency_p50_ms"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] in (
                "latency_p90_ms", "queue_wait_ms", "batch_fill",
                "prefill_ms", "decode_step_ms", "device_idle_share",
                "plan_s", "compile_s"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tiny cell, and a ``deploy`` that reuses one backend's engines
    (each run loads its seed's weights into them)."""
    from repro.core import profiler
    root = tiny_checkout(tmp_path_factory.mktemp("checkout"))
    cell = H.load_cell(root, CELL)
    mp = pytest.MonkeyPatch()
    # the planner would batch a model this small 32 at a time; plan over
    # batches the tiny engine warms up quickly
    mp.setattr(profiler, "Profiler",
               functools.partial(profiler.Profiler, batches=(1, 2, 4)))
    real_deploy, shared = H.deploy, {}

    def deploy(cell, dims, seed, backend=None):
        dep = real_deploy(cell, dims, seed, shared.get("backend"))
        shared["backend"] = dep.backend
        return dep

    mp.setattr(H, "deploy", deploy)
    try:
        yield cell, shared
    finally:
        mp.undo()


def _run(cell, trace=False, seed=SEED):
    return H.run(cell, seed, WINDOW_S, trace, time.monotonic(),
                 lambda msg: None)


def test_dropped_in_files_become_a_cell_and_a_metric(tiny):
    cell, _ = tiny
    assert cell.name == CELL and cell.rate_rps == 20.0
    assert "roots_due" in {m["name"] for m in cell.per_layer}
    out = _run(cell, trace=True)
    assert out["metrics"]["roots_due"] == {"value": 60.0, "unit": "req"}
    # what a CPU trace can give; device rates need the chip's peaks
    for name in ("queue_wait_ms", "batch_fill", "prefill_ms",
                 "decode_step_ms", "device_idle_share", "plan_s",
                 "compile_s"):
        assert name in out["metrics"], name
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    assert 0 < out["metrics"]["batch_fill"]["value"] <= 100


def test_a_run_prints_the_contracts_line(tiny):
    cell, _ = tiny
    out = _run(cell)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert out["attempted"] == 60
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p90_ms",
                                   "setup_s"}
    assert out["device"]["platform"] == "cpu"
    json.dumps(out, allow_nan=False)


def _roll_logits(decode):
    """Every decoded token becomes the next token id: a token altered
    where it is produced."""
    def wrong(params, cache, cache_len, tokens):
        logits, cache = decode(params, cache, cache_len, tokens)
        return jnp.roll(logits, 1, axis=-1), cache
    return wrong


def _stale_cache(decode):
    """The decode step returns its state unchanged."""
    def stale(params, cache, cache_len, tokens):
        logits, _ = decode(params, jax.tree.map(jnp.copy, cache),
                           cache_len, tokens)
        return logits, cache
    return stale


def _half_batch(decode):
    """Only the first half of the batch is computed; the other rows are
    answered with its logits."""
    def half(params, cache, cache_len, tokens):
        logits, cache = decode(params, cache, cache_len, tokens)
        b = logits.shape[0]
        return logits.at[b // 2:].set(logits[:b - b // 2]), cache
    return half


@pytest.mark.parametrize("fault", [_roll_logits, _stale_cache, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_a_fault_of_the_timed_path_is_not_correct(tiny, fault):
    cell, shared = tiny
    _run(cell)                         # builds the shared engines
    saved = {eng: eng.decode for eng in shared["backend"].served.values()}
    for eng, decode in saved.items():
        eng.decode = fault(decode)
    try:
        out = _run(cell, seed=SEED + 1)
    finally:
        for eng, decode in saved.items():
            eng.decode = decode
    assert out["correct"] is False
    gap = out["checks"]["max_gap.tiny-dense"]
    assert gap["value"] > gap["limit"]


def test_the_control_fails_where_the_program_passes(tiny):
    """The reference in fp8 put in the program's place, on three seeds
    at the cell's own load, judged by the cell's own limits as a run
    judges what it served."""
    from benchmarks.chip import control
    cell, _ = tiny
    with H.registered(cell) as dims:
        outs = list(control.readings(cell, dims, [SEED + 7, 11, 2 ** 40],
                                     WINDOW_S, ["fp8"], lambda m: None))
    for o in outs:
        program, fp8 = o["program"], o["control"]["fp8"]
        assert o["compiles_in_window"] == 0
        assert program["correct"] is True, o
        assert fp8["correct"] is False, o
        gap = fp8["checks"]["max_gap.tiny-dense"]
        assert gap["value"] > gap["limit"] == TINY_LIMIT
        assert gap["value"] > 3 * program["gaps"]["max_gap.tiny-dense"], o


def test_judge_holds_every_number_to_its_limit():
    limits = {"max_gap.a": 0.2, "max_gap.b": 0.2}
    numbers, ok = H.judge(limits, {"max_gap.a": 0.1, "max_gap.b": 0.2}, 0)
    assert ok and list(numbers) == ["compiles_in_window", "max_gap.a",
                                    "max_gap.b"]
    assert numbers["max_gap.a"] == {"value": 0.1, "limit": 0.2}
    assert not H.judge(limits, {"max_gap.a": 0.1, "max_gap.b": 0.5}, 0)[1]
    # a compile in the window, or a model that served nothing, fails
    assert not H.judge(limits, {"max_gap.a": 0.1, "max_gap.b": 0.1}, 1)[1]
    assert not H.judge(limits, {"max_gap.a": 0.1}, 0)[1]


def test_latency_runs_from_the_due_time():
    """A server that stalls the loop makes the roots due during the stall
    late; their latency counts the stall."""
    stall_s = 0.3

    async def main():
        async def handle(reader, writer):
            await reader.readuntil(b"\r\n\r\n")
            time.sleep(stall_s)        # blocks the loop, client and all
            body = b'{"status": "ok"}'
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n"
                         % len(body) + body)
            await writer.drain()
            writer.close()

        srv = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        due = np.array([0.01, 0.05, 0.1])
        try:
            return await TG.send_all("127.0.0.1", port, "app", due, 5.0,
                                     time.monotonic())
        finally:
            srv.close()
            await srv.wait_closed()

    roots = asyncio.run(main())
    assert [r.status for r in roots] == ["ok"] * 3
    for r in roots:
        assert r.latency_ms == pytest.approx((r.done_s - r.due_s) * 1e3)
    # the later roots were due while the first request blocked the loop
    assert roots[1].late_ms > 0.5 * stall_s * 1e3
    assert roots[2].latency_ms > roots[2].late_ms + stall_s * 1e3 * 0.9


@pytest.mark.parametrize("cell_name", sorted(
    w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())
    ["workloads"]))
def test_the_plan_is_the_same_across_seeds(cell_name):
    """Each cell's plan, at its own rate and published widths, is the same
    (task, variant, batch) servers whatever the run's seed, and no batch
    exceeds the engine's ``max_batch``."""
    from repro.gateway import build_demo_gateway
    cell = H.load_cell(REPO, cell_name)
    plans = []
    with H.registered(cell):
        graph = H.app_graph(cell.config, cell.traffic)
        for seed in (SEED, 7):
            gw, _ = build_demo_gateway(
                [graph], plan_rps=cell.rate_rps,
                s_avail=int(cell.config["s_avail"]), seed=seed)
            plans.append(sorted((s.tup.task, s.tup.variant, s.tup.batch)
                                for s in gw.servers))
    assert plans[0] == plans[1]
    assert {t for t, _, _ in plans[0]} == set(graph.tasks)
    assert max(b for _, _, b in plans[0]) <= \
        int(cell.config["engine"]["max_batch"])


def test_a_failed_root_counts_at_the_slo_or_later():
    """A root that did not come back ok ranks last, at the drain's end,
    in the median and the 90th percentile over every root; the share
    served is its own number."""
    roots = [TG.Root(0.0, 0.0, 0.3, "ok"), TG.Root(0.0, 0.0, 0.1, "dropped"),
             TG.Root(0.0, 0.0, 0.9, "dropped"),
             TG.Root(0.0, 0.0, 0.0, "rejected"), TG.Root(0.0)]
    w = H.Window(roots, 2.0, 0.0, H.Compiles(), {}, {}, [])
    assert H.root_latencies_ms(w, 5.0) == pytest.approx(
        [300.0, 7000.0, 7000.0, 7000.0, 7000.0])
    assert H.failed(w) == 4
    assert H.statuses(w) == {"dropped": 2, "ok": 1, "rejected": 1,
                             "undrained": 1}
    e2e = H.end_to_end(w, 650.0, 5.0, 1.0)
    assert e2e["goodput_rps"] == 0.5 and e2e["latency_p50_ms"] == 7000.0
    assert e2e["latency_p90_ms"] == 7000.0
    assert e2e["served_share"] == 20.0


def test_dropping_slow_roots_shows_in_the_share_served():
    """A change that drops the slowest roots lengthens the tail, where
    they rank last, and lowers the share served by as much."""
    def window(statuses):
        roots = [TG.Root(0.0, 0.0, t, s) for t, s in
                 zip(np.linspace(0.4, 0.8, len(statuses)), statuses)]
        return H.Window(roots, 10.0, 0.0, H.Compiles(), {}, {}, [])
    base = H.end_to_end(window(["ok"] * 20), 650.0, 5.0, 1.0)
    drop = H.end_to_end(window(["ok"] * 18 + ["dropped"] * 2), 650.0, 5.0,
                        1.0)
    assert drop["latency_p90_ms"] > base["latency_p90_ms"]
    assert drop["served_share"] == 90.0 and base["served_share"] == 100.0
    assert drop["latency_p50_ms"] >= base["latency_p50_ms"]


def test_the_sweeps_knee_rule():
    from benchmarks.chip import sweep
    line = {"statuses": {"ok": 30}, "first_third_late_p50_ms": 20.0,
            "last_third_late_p50_ms": 110.0, "first_third_ok_p50_ms": 580.0,
            "last_third_ok_p50_ms": 630.0}
    assert sweep.sustained(line)
    assert not sweep.sustained({**line, "statuses": {"undrained": 1}})
    assert not sweep.sustained({**line, "statuses": {"ok": 29,
                                                    "dropped": 1}})
    assert not sweep.sustained({**line, "last_third_late_p50_ms": 2000.0})
    assert not sweep.sustained({**line, "last_third_ok_p50_ms": 700.0})
    assert sweep.knee([(3.0, False), (2.0, True), (2.5, True)]) == 2.5
    assert sweep.knee([(2.0, True), (2.5, False), (3.0, True)]) == 2.0
    assert sweep.knee([(2.0, False)]) == 0.0


def test_due_times_are_the_same_set_in_another_order():
    a = TG.due_times(4.0, 50.0, 1)
    b = TG.due_times(4.0, 50.0, 2 ** 33 + 5)
    assert len(a) == len(b) == 200 and a[-1] == b[-1] == 50.0
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)


def _run_py(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ta-granite.steady", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_py_refuses_a_host_without_a_tpu():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert "correct" not in p.stdout


def test_run_py_fails_with_only_the_benchmarks_files(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for d in bench["paths"]:
        shutil.copytree(REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert "correct" not in p.stdout
