"""Prove the served path end to end on one TPU chip.

    python chip_smoke.py

One process runs every phase in order, and any failure exits non-zero:

1. **device**: the platform JAX reports must be ``tpu``. There is no CPU
   branch.
2. **serve**: a two-hop graph whose every variant is granite-3-2b is
   planned by the MILP. ``EngineBackend(full_width=True)`` serves it at
   published widths in bf16 behind the HTTP gateway on localhost. A burst
   of requests goes through the load generator's HTTP submitter. Each
   must come back ``ok`` within its deadline, ``/metrics`` must count
   every completion, and nothing may compile while serving.
3. **engine**: the engine's prefill-then-decode logits for one prompt
   are compared with ``Model.forward`` over the same tokens.
4. **kernels**: the flash and decode attention Pallas kernels, compiled
   by Mosaic at granite-3-2b widths, are compared with ``kernels/ref.py``.

Earlier lines report what ran. The last line of stdout is one JSON
object naming the device. The weights are random, drawn from a fixed
seed per arch.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS, ArchConfig  # noqa: E402
from repro.core.taskgraph import Task, TaskGraph, Variant  # noqa: E402
from repro.gateway import (GatewayHTTPServer,  # noqa: E402
                           build_demo_gateway, http_submitter)
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.obs import parse_exposition  # noqa: E402
from repro.runtime.backend import (EngineBackend,  # noqa: E402
                                   enable_compile_cache)

ARCH = "granite-3-2b"
# engine shapes: the compile test (tests/test_tpu_compile.py) checks that
# these prefill and decode programs fit one 16 GB v5e chip
PROMPT_LEN, MAX_NEW, MAX_BATCH, MAX_SEQ = 64, 8, 8, 512
N_REQUESTS = 8          # one burst: fills the largest warmed batch
SLO_MS = 2000.0
# the burst needs a feasible plan, not a capacity target
PLAN_RPS, S_AVAIL = 4.0, 16
REQUEST_TIMEOUT_S = 120.0
# bf16 keeps 8 significant bits, and prefill, decode and forward round the
# 40-layer bf16 residual stream in different orders, so their logits part
# by about 1% of the logit scale (0.7-1.5% measured at 12-40 layers on the
# CPU). A wrong position, cache slot or mask moves them by the scale itself.
LOGIT_RTOL = 0.05
# the bound tests/test_kernels.py holds bf16 kernels to: one bf16 rounding
# of the output plus f32 accumulation-order noise
KERNEL_ATOL = KERNEL_RTOL = 2e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


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
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def smoke_graph(prompt_len: int, max_new: int) -> TaskGraph:
    """classify feeds caption, both granite-3-2b, at the engine's shapes."""
    def task(name: str) -> Task:
        return Task(name, (Variant(ARCH, ARCH, accuracy=0.823,
                                   seq_len=prompt_len, gen_len=max_new),))
    return TaskGraph(name="granite_chain",
                     tasks={"classify": task("classify"),
                            "caption": task("caption")},
                     edges=[("classify", "caption")],
                     slo_latency_ms=SLO_MS, slo_accuracy=0.5)


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


async def _burst(gw: Any, hooks: Any, app: str,
                 n: int) -> Tuple[List[dict], str]:
    srv = GatewayHTTPServer(gw, hooks)
    await srv.start()
    try:
        submit = http_submitter(f"http://{srv.host}:{srv.port}")
        outcomes = await asyncio.wait_for(
            asyncio.gather(*(submit(app) for _ in range(n))),
            REQUEST_TIMEOUT_S)
        metrics = await _get(srv.host, srv.port, "/metrics")
    finally:
        await srv.stop()       # re-raises a backend fault
    return list(outcomes), metrics


def serve_phase(backend: EngineBackend,
                n_requests: int = N_REQUESTS) -> Dict[str, Any]:
    """Plan, build (params + warm-up compiles), then serve one burst."""
    graph = smoke_graph(backend.prompt_len, backend.max_new)
    t0 = time.monotonic()
    with compile_watch() as setup:
        gw, hooks = build_demo_gateway([graph], backend=backend,
                                       plan_rps=PLAN_RPS, s_avail=S_AVAIL)
    setup_s = time.monotonic() - t0
    with compile_watch() as serving:
        outcomes, metrics = asyncio.run(
            _burst(gw, hooks, graph.name, n_requests))
    completions = sum(parse_exposition(metrics)
                      .get("jigsaw_completions_total", {}).values())
    lat = sorted(o.get("latency_ms", float("nan")) for o in outcomes)
    out = {
        "setup_s": setup_s, "compiles_setup": setup.n,
        "compile_s": setup.seconds, "cache_hits": setup.cache_hits,
        "sent": n_requests,
        "ok": sum(o.get("status") == "ok" for o in outcomes),
        "dropped": sum(o.get("status") == "dropped" for o in outcomes),
        "deadline_met": sum(bool(o.get("deadline_met")) for o in outcomes),
        "completions": int(completions),
        "compiles_serving": serving.n,
        "latency_ms_p50": lat[len(lat) // 2], "latency_ms_max": lat[-1],
        "servers": sorted((s.tup.task, s.tup.segment, s.tup.batch)
                          for s in gw.servers),
    }
    if not (out["ok"] == out["deadline_met"] == out["completions"]
            == n_requests):
        raise SmokeFailure(f"serve: {out} outcomes={outcomes}")
    if serving.n:
        raise SmokeFailure(f"serve: {serving.n} compiles while serving")
    return out


def consistency_phase(engine: Any, prompt_len: int,
                      seed: int = 0) -> Dict[str, float]:
    """Prefill logits at the last prompt position, and the next decode
    step's, against one ``Model.forward`` over the same token sequence."""
    model, params = engine.model, engine.params
    S = prompt_len
    tokens = np.random.default_rng(seed).integers(
        0, model.arch.vocab_size, size=(1, S + 1), dtype=np.int32)
    lp, cache = engine.prefill(params, jnp.asarray(tokens[:, :S]))
    ld, _ = engine.decode(params, cache, jnp.int32(S),
                          jnp.asarray(tokens[:, S:]))
    ref = np.asarray(jax.jit(model.forward)(params, jnp.asarray(tokens)),
                     np.float32)
    out = {
        "scale": float(np.abs(ref).max()),
        "prefill": float(np.abs(np.asarray(lp, np.float32)[0, 0]
                                - ref[0, S - 1]).max()),
        "decode": float(np.abs(np.asarray(ld, np.float32)[0, 0]
                               - ref[0, S]).max()),
    }
    if max(out["prefill"], out["decode"]) > LOGIT_RTOL * out["scale"]:
        raise SmokeFailure(f"engine vs forward: {out}, "
                           f"tolerance {LOGIT_RTOL} x scale")
    return out


def _max_err(got: jax.Array, want: jax.Array) -> Tuple[float, bool]:
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.abs(g - w)
    return float(err.max()), bool(np.all(err <= KERNEL_ATOL
                                         + KERNEL_RTOL * np.abs(w)))


def kernel_phase(arch: ArchConfig, batch: int = 8, seq: int = 1024,
                 seed: int = 0) -> Dict[str, Any]:
    """Flash (causal prefill) and decode attention at ``arch``'s head
    widths, in bf16, against the f32 references."""
    H, KV, hd = arch.num_heads, arch.num_kv_heads, arch.head_dim
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (batch, seq, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq, KV, hd), jnp.bfloat16)
    qd = jax.random.normal(ks[3], (batch, 1, H, hd), jnp.bfloat16)
    cache_len = jnp.int32(seq * 3 // 4)     # a partly filled cache
    flash = kops.flash_attention(q, k, v, causal=True)
    dec = kops.decode_attention(qd, k, v, cache_len)
    with jax.default_matmul_precision("highest"):
        flash_ref = kref.flash_attention_ref(q, k, v, causal=True)
        dec_ref = kref.decode_attention_ref(qd, k, v, cache_len)
    f_err, f_ok = _max_err(flash, flash_ref)
    d_err, d_ok = _max_err(dec, dec_ref)
    hlo = (kops.flash_attention.lower(q, k, v).compile().as_text()
           + kops.decode_attention.lower(qd, k, v, cache_len)
           .compile().as_text())
    out = {"flash_err": f_err, "decode_err": d_err,
           "mosaic": "tpu_custom_call" in hlo}
    if not (f_ok and d_ok):
        raise SmokeFailure(f"kernels vs reference: {out}, tolerance "
                           f"atol {KERNEL_ATOL} + rtol {KERNEL_RTOL}")
    return out


def main() -> int:
    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {dev['platform']!r}")
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    backend = EngineBackend(full_width=True, max_batch=MAX_BATCH,
                            max_seq=MAX_SEQ, prompt_len=PROMPT_LEN,
                            max_new=MAX_NEW)
    srv = serve_phase(backend)
    eng = backend.engine_for(ARCH)
    a = eng.model.arch
    leaves = jax.tree.leaves(eng.params)
    print(f"model: {a.name} layers={a.num_layers} d_model={a.d_model} "
          f"heads={a.num_heads} kv_heads={a.num_kv_heads} "
          f"head_dim={a.head_dim} d_ff={a.d_ff} vocab={a.vocab_size} "
          f"dtype={leaves[0].dtype} "
          f"weights_gb={sum(x.nbytes for x in leaves) / 1e9:.3f}")
    print(f"engine: max_batch={MAX_BATCH} max_seq={MAX_SEQ} "
          f"prompt_len={PROMPT_LEN} max_new={MAX_NEW}")
    print(f"plan: servers (task, segment, batch) {srv['servers']}")
    print(f"setup: {srv['setup_s']:.3f} s wall, of which compile "
          f"{srv['compile_s']:.3f} s over {srv['compiles_setup']} "
          f"programs ({srv['cache_hits']} persistent-cache hits)")
    print(f"serve: sent={srv['sent']} ok={srv['ok']} "
          f"dropped={srv['dropped']} deadline_met={srv['deadline_met']} "
          f"metrics_completions={srv['completions']} "
          f"compiles_while_serving={srv['compiles_serving']} "
          f"latency_ms p50={srv['latency_ms_p50']:.1f} "
          f"max={srv['latency_ms_max']:.1f} (slo {SLO_MS:.0f})", flush=True)

    con = consistency_phase(eng, PROMPT_LEN)
    print(f"engine vs forward (bf16): max|dlogit| prefill="
          f"{con['prefill']:.5f} decode={con['decode']:.5f} "
          f"logit scale={con['scale']:.4f} "
          f"tolerance={LOGIT_RTOL * con['scale']:.5f}", flush=True)

    ker = kernel_phase(ARCHS[ARCH])
    print(f"kernels (bf16 vs f32 ref): flash max|err|={ker['flash_err']:.5f}"
          f" decode max|err|={ker['decode_err']:.5f} "
          f"mosaic={ker['mosaic']}", flush=True)
    if not ker["mosaic"]:
        raise SmokeFailure("kernels did not lower to a Mosaic custom call")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
