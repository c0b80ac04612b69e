"""The per-layer metrics that read the program's own counters through the
``/metrics`` scrapes around the window (``loop_replay_ms``,
``loop_batch_wait_ms``, ``generate_host_ms``): on the tiny cell of
``test_bench_chip_harness.py`` each reads a value, and the loop's four
states between the two scrapes add up to the wall time between them; on a
program without the counters each reads nothing and does not raise."""
import functools
import json
import sys
import time
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))
from benchmarks.chip import harness as H  # noqa: E402
from test_bench_chip_harness import (CELL, SEED, WINDOW_S,  # noqa: E402
                                     tiny_checkout)

NEW = ("loop_replay_ms", "loop_batch_wait_ms", "generate_host_ms")
LOOP = "jigsaw_gateway_loop_seconds_total"


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """The tiny cell, with the three metrics listed for it too."""
    from repro.core import profiler
    root = tiny_checkout(tmp_path_factory.mktemp("checkout"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    mp = pytest.MonkeyPatch()
    mp.setattr(profiler, "Profiler",
               functools.partial(profiler.Profiler, batches=(1, 2, 4)))
    try:
        yield H.load_cell(root, CELL)
    finally:
        mp.undo()


def test_the_program_counters_read_in_the_tiny_cell(cell, monkeypatch):
    scraped, seen = [], {}
    get, per_layer = H._get, H.per_layer

    async def timed_get(host, port, path):
        text = await get(host, port, path)
        if path == "/metrics":
            scraped.append(time.monotonic())
        return text

    def keep(ctx):
        seen["window"] = ctx.window
        return per_layer(ctx)

    monkeypatch.setattr(H, "_get", timed_get)
    monkeypatch.setattr(H, "per_layer", keep)
    out = H.run(cell, SEED, WINDOW_S, True, time.monotonic(),
                lambda msg: None)
    got = {n: out["metrics"][n]["value"] for n in NEW}
    assert got["loop_replay_ms"] > 0 and got["loop_batch_wait_ms"] >= 0
    assert got["generate_host_ms"] > 0
    w = seen["window"]
    states = [k for k in w.metrics_after if k.startswith(LOOP + "{")]
    assert len(states) == 4
    loop_s = sum(w.metrics_after[k] - w.metrics_before[k] for k in states)
    assert len(scraped) == 2
    assert loop_s == pytest.approx(scraped[1] - scraped[0], rel=0.01)
    assert w.metrics_after["jigsaw_compiles_total"] >= \
        w.metrics_before["jigsaw_compiles_total"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_nothing(name):
    roots = 'jigsaw_arrivals_total{app="traffic_analysis"}'
    window = H.Window([], 1.0, 0.0, H.Compiles(), {roots: 2.0},
                      {roots: 9.0}, [])
    read = H.load_reader(HERE.parents[1] / "benchmarks" / "chip", name)
    assert read(types.SimpleNamespace(window=window), None) is None
