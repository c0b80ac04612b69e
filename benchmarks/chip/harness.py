"""The phases of one benchmark run, driven by the files named in
``BENCHMARK.json``.

A cell ``<config>.<mix>`` names a configuration file
(``configs/<config>.json``: the models at their published widths, the app
they serve, the deployment's sizes and the limits of the comparison) and a
traffic mix (``traffic/<mix>.json``: arrivals, rate, lengths). The
configuration names its model family, whose module
(``families/<family>.py``, see ``families/__init__.py``) draws the weights,
computes the plain reference and counts the work. A per-layer metric
``<name>[.<part>]`` is read by ``metrics/<name>.py``. Adding a cell, a
configuration, a family or a metric adds files and entries, never edits
these.

The system under test is the repo's own serving path:
``build_demo_gateway`` plans the app with the MILP and wraps the plan in
an ``AsyncGateway`` behind ``GatewayHTTPServer``; ``EngineBackend`` serves
each batch on a bf16 ``Engine`` of the model at published widths. The
benchmark gives the engines weights drawn from the run's seed, records
what each ``generate`` call served, and wraps the calls in profiler spans.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import (Any, Callable, Dict, Iterator, List, Mapping, Optional,
                    Tuple)

import numpy as np

from benchmarks.chip import correct as C
from benchmarks.chip import trace_reduce as TR
from benchmarks.chip import traffic_gen as TG
from benchmarks.chip import weights as W

HOST_SPANS = ("window", "generate|", "service_s|", "plan")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

class BenchError(RuntimeError):
    """The cell cannot be run as its files describe it."""


# ---------------------------------------------------------------------------
# compiles and device
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Compiles:
    """XLA compiles seen inside a :func:`compile_watch` block."""
    n: int = 0
    seconds: float = 0.0
    cache_hits: int = 0


@contextlib.contextmanager
def compile_watch() -> Iterator[Compiles]:
    """Count every XLA compile (jitted or eager, persistent-cache hits
    included) and its seconds inside the block."""
    import jax
    seen = Compiles()

    def on_duration(event: str, duration: float, **_: Any) -> None:
        if event == _COMPILE_EVENT:
            seen.n += 1
            seen.seconds += duration

    def on_event(event: str, **_: Any) -> None:
        if event == _CACHE_HIT_EVENT:
            seen.cache_hits += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(key: str = "peak_bytes_in_use") -> int:
    """The peak of the fullest device (0 where the backend keeps none)."""
    import jax
    return max(int((d.memory_stats() or {}).get(key, 0))
               for d in jax.devices())


def enable_compile_cache(checkout: Path) -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, a fixed path,
    with every program cached, however short its compile or small its
    entry."""
    import jax
    path = str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# the files of a cell
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    home: Path                           # the benchmark's own directory
    chips: int
    config: Dict[str, Any]
    family: Any                          # the module families/<family>.py
    traffic: Dict[str, Any]
    per_layer: List[Dict[str, Any]]      # BENCHMARK.json entries that apply
    end_to_end: List[Dict[str, Any]]

    @property
    def rate_rps(self) -> float:
        return float(self.traffic["rate_of_knee"]) * float(
            self.config["knee_rps"])


def _applies(entry: Mapping[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    home = root / Path(bench["command"][1]).parent
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    if "family" not in config:
        raise BenchError(f"configuration {w['config']!r} names no family")
    family = load_family(home, config["family"])
    traffic = json.loads((home / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    if traffic.get("arrivals") not in TG.ARRIVALS:
        raise BenchError(f"traffic {w['traffic']!r}: arrivals "
                         f"{traffic.get('arrivals')!r} not in {TG.ARRIVALS}")
    return Cell(workload, home, int(w["chips"]), config, family, traffic,
                [m for m in bench["per_layer"] if _applies(m, workload)],
                [m for m in bench["end_to_end"] if _applies(m, workload)])


def load_family(home: Path, name: str) -> Any:
    """The module ``families/<name>.py`` of the benchmark at ``home``,
    loaded once per process: every ``Dims`` of a family is then of one
    class, which ``weights.family_of`` maps back to the module."""
    path = home / "families" / f"{name}.py"
    if not str(name).isidentifier() or not path.is_file():
        raise BenchError(f"family {name!r}: no module {path}")
    modname = f"benchmarks.chip.families.{name}"
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    return sys.modules[modname]


@contextlib.contextmanager
def registered(cell: Cell) -> Iterator[Dict[str, Any]]:
    """The cell's model as a repo arch under its ``served_as`` name, for
    the planner's profiler and the engines; a name the repo already has
    must describe the same arch. Yields the family's dims of the model by
    that name, and unregisters what it added on exit."""
    from repro.configs import ARCHS
    config = cell.config
    name = config["served_as"]
    dims = cell.family.Dims.from_keys(name, config)
    try:
        arch = cell.family.repo_arch(dims, config)
    except ValueError as e:
        raise BenchError(f"{name}: {e}") from e
    added = name not in ARCHS
    if added:
        ARCHS[name] = arch
    elif ARCHS[name] != arch:
        raise BenchError(f"{name}: the config file differs from the "
                         f"repo's arch {ARCHS[name]}")
    try:
        yield {name: dims}
    finally:
        if added:
            ARCHS.pop(name, None)


def app_graph(config: Mapping[str, Any], traffic: Mapping[str, Any]):
    """The config's app with one bf16 variant per task, the config's
    model, at the mix's lengths, so that the planner models what the
    engine serves, under the config's latency SLO ``slo_ms``."""
    from repro.core.apps import get_app
    from repro.core.taskgraph import Task, TaskGraph, Variant
    base = get_app(config["app"])
    if set(config["tasks"]) != set(base.tasks):
        raise BenchError(f"tasks {sorted(config['tasks'])} are not the "
                         f"app's {sorted(base.tasks)}")
    a = config["served_as"]
    tasks = {t: Task(t, (Variant(a, a, accuracy=base.tasks[t].max_accuracy,
                                 seq_len=int(traffic["prompt_len"]),
                                 gen_len=int(traffic["max_new"])),))
             for t in base.tasks}
    mult = {(u, a, v): float(config["fanout"][v]) for u, v in base.edges}
    return TaskGraph(name=base.name, tasks=tasks, edges=list(base.edges),
                     mult=mult, slo_latency_ms=float(config["slo_ms"]),
                     slo_accuracy=base.slo_accuracy,
                     path_fractions=dict(base.path_fractions))


# ---------------------------------------------------------------------------
# the backend: the repo's EngineBackend with the benchmark's weights
# ---------------------------------------------------------------------------
def bench_backend_class():
    """``EngineBackend`` with three additions on the benchmark's side:
    weights from the seed, a record of what each ``generate`` served, and
    profiler spans around ``service_s`` and ``generate``. A batch larger
    than ``max_batch`` fails the run: the backend would time one launch
    and multiply it by launches that never ran."""
    import jax
    from repro.runtime.backend import EngineBackend

    @dataclasses.dataclass
    class BenchBackend(EngineBackend):
        dims: Dict[str, Any] = dataclasses.field(default_factory=dict)
        words: Any = None
        # (arch, prompts [b, S], served tokens [b, max_new]) per generate
        calls: List[Tuple[str, np.ndarray, np.ndarray]] = dataclasses.field(
            default_factory=list)
        served: Dict[str, Any] = dataclasses.field(default_factory=dict)
        bind_t: float = math.nan      # monotonic time the first bind began

        def bind(self, graph, config, app=""):
            if math.isnan(self.bind_t):
                self.bind_t = time.monotonic()
            big = max(tup.batch for tup, _ in config.instances())
            if big > self.max_batch:
                raise BenchError(f"the plan batches {big} but the engine "
                                 f"holds max_batch {self.max_batch}")
            super().bind(graph, config, app)
            for arch in sorted({graph.tasks[tup.task].variant(tup.variant)
                                .arch for tup, _ in config.instances()}):
                if arch not in self.served:
                    self._adopt(arch)
                self.load_weights(arch)

        def _adopt(self, arch: str) -> None:
            eng = self.engine_for(arch)
            generate = eng.generate

            def recorded(prompts, max_new=16, eos_id=None):
                with jax.profiler.TraceAnnotation(
                        f"generate|{arch}|{len(prompts)}"):
                    out = generate(prompts, max_new=max_new, eos_id=eos_id)
                self.calls.append((arch, np.array(prompts), np.array(out)))
                return out

            eng.generate = recorded
            self.served[arch] = eng

        def load_weights(self, arch: str) -> None:
            """Replace the engine's weights with the seed's draw, freeing
            the old ones first; the tree must be the one the engine's
            programs were compiled for."""
            eng = self.served[arch]
            if eng.params is not None:
                want = jax.tree.map(lambda a: (a.shape, a.dtype), eng.params)
            else:
                want = None
            eng.params = None
            # wait for the draw: an old tree is freed only once no program
            # in flight still uses it
            m = self.dims[arch]
            eng.params = jax.block_until_ready(
                W.family_of(m).served_params(m, self.words))
            got = jax.tree.map(lambda a: (a.shape, a.dtype), eng.params)
            if want is not None and got != want:
                raise BenchError(f"{arch}: the benchmark's weights are not "
                                 f"the tree the engine serves")

        def release(self) -> None:
            for eng in self.served.values():
                eng.params = None

        def service_s(self, server, batch, now_s, rng):
            if len(batch) > self.max_batch:
                raise BenchError(f"a batch of {len(batch)} exceeds "
                                 f"max_batch {self.max_batch}")
            with jax.profiler.TraceAnnotation(
                    f"service_s|{server.tup.task}"):
                return super().service_s(server, batch, now_s, rng)

    return BenchBackend


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Deployment:
    cell: Cell
    backend: Any
    gateway: Any
    hooks: Any
    graph: Any
    plan_s: float
    compiles: Compiles

    def servers(self) -> List[Tuple[str, str, int]]:
        return sorted((s.tup.task, s.tup.variant, s.tup.batch)
                      for s in self.gateway.servers)


def deploy(cell: Cell, dims: Dict[str, Any], seed: int,
           backend: Any = None) -> Deployment:
    """Plan, build and warm up; with ``backend``, reuse its engines and
    load the new seed's weights. The gateway takes the config's
    ``staleness_ms``, its constructor's allowance for the early-drop
    rule's staleness test."""
    import jax
    from repro.gateway import build_demo_gateway
    cfg, tr = cell.config, cell.traffic
    if backend is None:
        eng = cfg["engine"]
        if int(tr["prompt_len"]) + int(tr["max_new"]) > int(eng["max_seq"]):
            raise BenchError("prompt_len + max_new exceeds max_seq")
        backend = bench_backend_class()(
            full_width=bool(eng.get("full_width", True)),
            max_batch=int(eng["max_batch"]), max_seq=int(eng["max_seq"]),
            prompt_len=int(tr["prompt_len"]), max_new=int(tr["max_new"]),
            dims=dims)
    backend.words = W.seed_words(seed)
    backend.bind_t = math.nan
    graph = app_graph(cfg, tr)
    t0 = time.monotonic()
    with compile_watch() as seen, jax.profiler.TraceAnnotation("plan"):
        gw, hooks = build_demo_gateway(
            [graph], backend=backend, plan_rps=cell.rate_rps,
            s_avail=int(cfg["s_avail"]), seed=seed)
    gw.staleness_ms = float(cfg["staleness_ms"])
    return Deployment(cell, backend, gw, hooks, graph,
                      backend.bind_t - t0, seen)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Window:
    roots: List[TG.Root]
    seconds: float
    start_monotonic: float
    compiles: Compiles
    metrics_before: Dict[str, float]
    metrics_after: Dict[str, float]
    queue_ms: List[float]            # gateway queue spans (ms)
    trace_dir: Optional[str] = None


def parse_exposition(text: str) -> Dict[str, float]:
    """Prometheus text -> {'name{labels}': value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


async def _get(host: str, port: int, path: str) -> str:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                     f"Connection: close\r\n\r\n".encode())
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
        await writer.wait_closed()
    return raw.partition(b"\r\n\r\n")[2].decode()


async def _serve(dep: Deployment, due: np.ndarray, drain_s: float,
                 trace_span: Optional[Tuple[float, float]],
                 trace_dir: Optional[str], lead_s: float
                 ) -> Tuple[List[TG.Root], float, Dict[str, float],
                            Dict[str, float]]:
    import jax
    from repro.gateway import GatewayHTTPServer
    srv = GatewayHTTPServer(dep.gateway, dep.hooks)
    await srv.start()
    try:
        before = parse_exposition(await _get(srv.host, srv.port, "/metrics"))
        t0 = time.monotonic() + lead_s

        async def tracer() -> None:
            assert trace_span is not None and trace_dir is not None
            await asyncio.sleep(max(t0 + trace_span[0] - time.monotonic(),
                                    0.0))
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
                await asyncio.sleep(max(t0 + trace_span[1]
                                        - time.monotonic(), 0.0))

        tr_task = (asyncio.create_task(tracer()) if trace_span is not None
                   else None)
        try:
            roots = await TG.send_all(srv.host, srv.port, dep.graph.name,
                                      due, drain_s, t0)
        finally:
            if tr_task is not None:
                await tr_task
                # collecting the trace blocks the loop for seconds: stop
                # only once every root has drained
                jax.profiler.stop_trace()
        after = parse_exposition(await _get(srv.host, srv.port, "/metrics"))
    finally:
        await srv.stop()
    return roots, t0, before, after


def serve_window(dep: Deployment, seed: int, seconds: float, trace: bool,
                 lead_s: float = 0.05) -> Window:
    """Serve one open-loop window over HTTP on localhost. With ``trace``,
    the profiler records the window's last seconds."""
    tr = dep.cell.traffic
    due = TG.due_times(dep.cell.rate_rps, seconds, seed)
    span = None
    trace_dir = None
    if trace:
        length = min(float(tr.get("trace_s", 6.0)), seconds / 2)
        span = (seconds - length, seconds)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    if dep.hooks.tracer is not None:
        dep.hooks.tracer.clear()
    with compile_watch() as seen:
        roots, t0, before, after = asyncio.run(_serve(
            dep, due, float(tr["drain_s"]), span, trace_dir, lead_s))
    queue_ms = [(s.end_s - s.start_s) * 1e3
                for s in (dep.hooks.tracer.spans if dep.hooks.tracer else [])
                if s.cat == "queue"]
    return Window(roots, seconds, t0, seen, before, after, queue_ms,
                  trace_dir)


# ---------------------------------------------------------------------------
# end-to-end numbers
# ---------------------------------------------------------------------------
def root_latencies_ms(w: Window, drain_s: float) -> List[float]:
    """The latency of every root due in the window, as its client saw it:
    due time to final answer. A root that did not come back ok (dropped,
    refused, errored, never answered) ranks last, at the drain's end,
    ``(seconds + drain_s) * 1000`` ms, so that dropping a slow root never
    shortens the quantiles that are taken over every root."""
    cap = (w.seconds + drain_s) * 1e3
    return [r.latency_ms if r.status == "ok" else cap for r in w.roots]


def end_to_end(w: Window, slo_ms: float, drain_s: float,
               setup_s: float) -> Dict[str, float]:
    """The median and 90th percentile over every root due, failed ones
    ranked last; the share of roots served ok as ``served_share``, which
    the sweep prints."""
    served = [r.latency_ms for r in w.roots if r.status == "ok"]
    good = sum(ms <= slo_ms for ms in served)
    every = root_latencies_ms(w, drain_s)
    return {"goodput_rps": good / w.seconds,
            "latency_p50_ms": float(np.percentile(every, 50)),
            "latency_p90_ms": float(np.percentile(every, 90)),
            "served_share": 100.0 * len(served) / len(w.roots),
            "setup_s": setup_s}


def failed(w: Window) -> int:
    return sum(r.status != "ok" for r in w.roots)


def statuses(w: Window) -> Dict[str, int]:
    """How many roots ended in each status."""
    out: Dict[str, int] = {}
    for r in w.roots:
        out[r.status] = out.get(r.status, 0) + 1
    return dict(sorted(out.items()))


def drops_by_reason(w: Window) -> Dict[str, float]:
    """The gateway's hop drops in the window by reason, from ``/metrics``
    before and after it."""
    out: Dict[str, float] = {}
    for key, after in w.metrics_after.items():
        if key.split("{", 1)[0] != "jigsaw_drops_total":
            continue
        n = after - w.metrics_before.get(key, 0.0)
        if n:
            reason = key.partition('reason="')[2].partition('"')[0]
            out[reason] = out.get(reason, 0.0) + n
    return dict(sorted(out.items()))


def lateness_line(w: Window) -> str:
    late = sorted(r.late_ms for r in w.roots if not math.isnan(r.sent_s))
    if not late:
        return "generator: nothing sent"
    q = lambda p: late[min(int(p * len(late)), len(late) - 1)]
    return (f"generator lateness ms: p50={q(0.5)} p99={q(0.99)} "
            f"max={late[-1]} over {len(late)} sends")


# ---------------------------------------------------------------------------
# per-layer numbers, from the traced run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Execution:
    """One program execution on the device inside the traced window."""
    arch: str
    kind: str            # prefill | decode
    batch: int
    cache_len: int       # decode: cached positions before the step
    seconds: float
    whole: bool          # its generate call lies wholly in the window


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Cell
    dims: Dict[str, Any]
    window: Window
    peak: Dict[str, float]
    memory_peak_bytes: int
    plan_s: float
    compile_s: float
    busy_s: Optional[float] = None
    window_s: Optional[float] = None
    executions: List[Execution] = dataclasses.field(default_factory=list)
    # (arch, batch, seconds) of each generate inside the traced window
    generates: List[Tuple[str, int, float]] = dataclasses.field(
        default_factory=list)

    def flops(self, e: Execution) -> float:
        from benchmarks.chip import work
        m = self.dims[e.arch]
        if e.kind == "prefill":
            return work.prefill_flops(m, e.batch,
                                      int(self.cell.traffic["prompt_len"]))
        return work.decode_flops(m, e.batch, e.cache_len)


def attribute(trace: TR.Trace, window: TR.Interval, prompt_len: int
              ) -> Tuple[List[Execution], List[Tuple[str, int, float]]]:
    """Name each prefill and decode execution in ``window`` by the
    ``generate|<arch>|<batch>`` span that ran it; the i-th decode of a
    generate steps from ``prompt_len + i`` cached positions."""
    gens = [ev for ev in trace.host if ev.name.startswith("generate|")]
    inside = [g for g in gens
              if g.start_ns >= window[0] and g.end_ns <= window[1]]
    whole = set(inside)
    steps: Dict[TR.Event, int] = {}
    out = []
    for ev in trace.executions:
        if not (window[0] <= ev.start_ns and ev.end_ns <= window[1]):
            continue
        low = ev.name.lower()
        kind = ("prefill" if "prefill" in low
                else "decode" if "decode" in low else None)
        if kind is None:
            continue
        g = TR.enclosing(ev, gens)
        if g is None:
            continue
        _, arch, batch = g.name.split("|")
        i = 0
        if kind == "decode":
            i = steps.get(g, 0)
            steps[g] = i + 1
        out.append(Execution(arch, kind, int(batch), prompt_len + i,
                             (ev.end_ns - ev.start_ns) / 1e9, g in whole))
    return out, [(g.name.split("|")[1], int(g.name.split("|")[2]),
                  (g.end_ns - g.start_ns) / 1e9) for g in inside]


def load_reader(home: Path, name: str
                ) -> Callable[[Context, Optional[str]], Optional[float]]:
    """``metrics/<base>.py``'s ``read``, for the metric ``<base>[.<part>]``."""
    base = name.split(".", 1)[0]
    path = home / "metrics" / f"{base}.py"
    if not path.is_file():
        raise BenchError(f"metric {name!r}: no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(ctx: Context) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in ctx.cell.per_layer:
        part = m["name"].split(".", 1)[1] if "." in m["name"] else None
        v = load_reader(ctx.cell.home, m["name"])(ctx, part)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def reduce_trace(ctx: Context, on_tpu: bool) -> Dict[str, Any]:
    """Fill ``ctx`` from the traced window; returns the breakdown. A trace
    with no TPU plane (on a TPU), no device operation or no prefill or
    decode execution in the window fails the run: the device metrics
    would read nothing, or an idle share of 100%."""
    trace = TR.load(TR.find_xplane(ctx.window.trace_dir), HOST_SPANS)
    if on_tpu and trace.devices == 0:
        raise BenchError("the trace holds no TPU device plane")
    win = TR.window_of(trace)
    ctx.window_s = (win[1] - win[0]) / 1e9
    ctx.busy_s = TR.busy_s(trace, win)
    ctx.executions, ctx.generates = attribute(
        trace, win, int(ctx.cell.traffic["prompt_len"]))
    if not ctx.busy_s or not ctx.executions:
        raise BenchError(f"the traced window held {ctx.busy_s} s of device "
                         f"work and {len(ctx.executions)} prefill or decode "
                         f"executions")
    label = lambda n: n.rsplit("|", 1)[0] if n.startswith("generate|") \
        else n
    return {"device_ops": TR.top(TR.op_seconds(trace, win)),
            "idle_gaps": TR.top(TR.idle_by_host(trace, win, label))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def judge(limits: Mapping[str, float], checks: Mapping[str, float],
          compiles_in_window: int) -> Tuple[Dict[str, Dict[str, Any]], bool]:
    """Each number compared beside its limit, and whether the run is
    ``correct``: no compile in the window, and every number the config
    limits at or under its limit. A model the window never served has no
    number, and fails."""
    numbers: Dict[str, Dict[str, Any]] = {
        "compiles_in_window": {"value": compiles_in_window, "limit": 0}}
    for key, limit in sorted(limits.items()):
        numbers[key] = {"value": checks.get(key), "limit": float(limit)}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in numbers.values())
    return numbers, ok


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        process_start: float, log: Callable[[str], None]) -> Dict[str, Any]:
    """Set up, serve the window, check ``correct``; the result's line."""
    from benchmarks.chip import peaks
    dev = device_info()
    peak = peaks.peak(dev["kind"]) if dev["platform"] == "tpu" else {}
    tr = cell.traffic
    with registered(cell) as dims:
        dep = deploy(cell, dims, seed)
        log(f"plan: rate {cell.rate_rps} rps (knee "
            f"{cell.config['knee_rps']} x {tr['rate_of_knee']}), s_avail "
            f"{cell.config['s_avail']}, servers (task, variant, batch) "
            f"{dep.servers()}")
        log(f"setup: plan {dep.plan_s} s, {dep.compiles.n} compiles "
            f"({dep.compiles.cache_hits} persistent-cache hits) in "
            f"{dep.compiles.seconds} s")
        log(f"device memory after set-up: in use "
            f"{memory_peak_bytes('bytes_in_use')} B, peak "
            f"{memory_peak_bytes()} B")
        w = serve_window(dep, seed, seconds, trace)
        setup_s = w.start_monotonic - process_start
        log(lateness_line(w))
        log(f"roots by status: {statuses(w)}; hop drops by reason: "
            f"{drops_by_reason(w)}")
        mem = memory_peak_bytes()
        ctx = Context(cell, dims, w, peak, mem, dep.plan_s,
                      dep.compiles.seconds)
        breakdown = None
        if trace:
            try:
                breakdown = reduce_trace(ctx, dev["platform"] == "tpu")
            finally:
                shutil.rmtree(w.trace_dir, ignore_errors=True)
        calls = dep.backend.calls
        dep.backend.release()
        del dep
        t_ref = time.monotonic()
        checks = C.compare_calls(
            dims, W.seed_words(seed), calls, seed,
            int(cell.config["sample_rows"]), int(tr["prompt_len"]))
        log(f"reference: {time.monotonic() - t_ref} s over "
            f"{len(calls)} generate calls served")
    log(f"gap numbers: {json.dumps(checks)}")
    numbers, ok = judge(cell.config["limits"], checks, w.compiles.n)
    slo = float(cell.config["slo_ms"])
    result: Dict[str, Any] = {
        "correct": ok, "attempted": len(w.roots), "failed": failed(w)}
    if trace:
        result["metrics"] = per_layer(ctx)
        result["device"] = {**dev, "memory_peak_bytes": mem,
                            "busy_s": ctx.busy_s, "window_s": ctx.window_s}
        result["breakdown"] = breakdown
    else:
        e2e = end_to_end(w, slo, float(tr["drain_s"]), setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = {**dev, "memory_peak_bytes": mem}
    result["checks"] = numbers
    return result
