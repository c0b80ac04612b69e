"""Serving front door (DESIGN.md §14): in-process AsyncGateway
end-to-end over two apps, ladder admission at the door, and the stdlib
HTTP server (submit / stream / metrics / trace) on an ephemeral port.

All async tests run through ``asyncio.run`` directly — no pytest-asyncio
in the image.  Gateways run time-compressed (``time_scale < 1``) so a
multi-second simulated serve finishes in a fraction of a wall second;
scales are chosen gentle enough that event-loop overhead (amplified by
1/time_scale in simulated terms) does not flood the deadline budget.
"""
import asyncio
import json

import pytest

from repro.core.dispatch import QueuedRequest
from repro.core.milp import Planner
from repro.gateway import (AdmissionRejected, AsyncGateway,
                           GatewayHTTPServer, direct_submitter,
                           http_submitter, open_loop)
from repro.obs import (Instrumentation, Tracer, parse_exposition,
                       validate_chrome_trace)


@pytest.fixture(scope="module")
def planned_apps(social_profiler, traffic_profiler):
    out = {}
    for name, (g, prof) in (("social_media", social_profiler),
                            ("traffic_analysis", traffic_profiler)):
        cfg = Planner(g, prof, s_avail=64, max_tuples_per_task=32,
                      bb_nodes=4, bb_time_s=1.0).plan(30.0)
        assert cfg is not None
        out[name] = (g, cfg)
    return out


def test_gateway_end_to_end_two_apps(planned_apps):
    """Open-loop load over both apps: every submitted request resolves,
    the scraped counters are self-consistent with the load report, and
    completed requests carry one hop span per task executed."""
    hooks = Instrumentation(tracer=Tracer())

    async def drive():
        gw = AsyncGateway(planned_apps, seed=0, hooks=hooks,
                          time_scale=0.2)
        await gw.start()
        try:
            report = await open_loop(
                direct_submitter(gw),
                {"social_media": 8.0, "traffic_analysis": 8.0},
                duration_s=3.0, seed=1, time_scale=gw.time_scale)
        finally:
            await gw.stop()
        return gw, report

    gw, report = asyncio.run(drive())
    d = report.to_dict()
    tot = d["total"]
    assert tot["submitted"] > 10
    # every submission resolved one way: ok, dropped, or rejected
    assert tot["ok"] + tot["dropped"] + tot["rejected"] == tot["submitted"]
    assert tot["errors"] == 0
    assert tot["ok"] > 0 and tot["attainment"] > 0.5
    assert not gw._roots, "no request may leak in the root table"

    parsed = parse_exposition(hooks.registry.render())
    arrivals = parsed["jigsaw_arrivals_total"]
    for app in planned_apps:
        st = d["apps"][app]
        accepted = st["submitted"] - st["rejected"]
        assert arrivals.get((("app", app),), 0) == accepted
    # completions counts roots finalized at a leaf: every fully-ok root
    # plus the partially-dropped ones whose last hop still completed
    comp = sum(parsed.get("jigsaw_completions_total", {}).values())
    assert tot["ok"] <= comp <= tot["ok"] + tot["dropped"]

    # trace: valid chrome JSON; a completed root has >= 1 hop span and
    # matching queue/service sub-spans
    events = validate_chrome_trace(hooks.tracer.chrome_trace())
    assert events
    roots_with_hops = {s.root_id for s in hooks.tracer.spans_for_root(0)}
    for rid in range(tot["submitted"]):
        hops = hooks.tracer.spans_for_root(rid, cat="hop")
        if hops:
            assert len(hooks.tracer.spans_for_root(rid, "queue")) == \
                len(hops)
            assert len(hooks.tracer.spans_for_root(rid, "service")) == \
                len(hops)
            break
    else:
        pytest.fail("no root produced hop spans")


def test_gateway_admission_rejects_on_full_queue(planned_apps):
    """The level-1 ladder rung guards the door: an entry queue past the
    SLO-feasible depth refuses new submissions with a 'admission'."""
    hooks = Instrumentation()

    async def drive():
        gw = AsyncGateway(planned_apps, seed=0, hooks=hooks,
                          time_scale=1.0)
        # stuff the entry queue well past any feasible cap — without
        # starting dispatchers, so the backlog cannot drain
        app = "social_media"
        g, _ = planned_apps[app]
        qt = f"{app}::{g.entry}"
        now = gw.now()
        gw.queues[qt].extend(
            QueuedRequest(10_000 + i, 10_000 + i, qt, now, now + 10.0)
            for i in range(10_000))
        with pytest.raises(AdmissionRejected) as ei:
            await gw.submit(app)
        assert ei.value.reason == "admission"
        # the other app's door stays open
        gr = await gw.submit("traffic_analysis")
        assert gr.root_id >= 0

    asyncio.run(drive())
    parsed = parse_exposition(hooks.registry.render())
    rejects = parsed["jigsaw_admission_rejects_total"]
    assert rejects[(("app", "social_media"),)] == 1
    assert parsed["jigsaw_drops_total"][
        (("app", "social_media"), ("reason", "admission"))] == 1


def test_gateway_quota_rejects_over_contracted_rate(planned_apps):
    """The per-app token bucket refuses arrivals beyond the contracted
    rps with reason 'quota' — BEFORE the ladder's load gate, and only
    for the quota'd app."""
    hooks = Instrumentation()

    async def drive():
        gw = AsyncGateway(planned_apps, seed=0, hooks=hooks,
                          time_scale=1.0,
                          quotas={"social_media": 0.01}, quota_burst=2.0)
        # the bucket banks one burst at t=0: 2 admits, then refusal
        await gw.submit("social_media")
        await gw.submit("social_media")
        with pytest.raises(AdmissionRejected) as ei:
            await gw.submit("social_media")
        assert ei.value.reason == "quota"
        # the un-quota'd app's door stays open
        gr = await gw.submit("traffic_analysis")
        assert gr.root_id >= 0

    asyncio.run(drive())
    parsed = parse_exposition(hooks.registry.render())
    assert parsed["jigsaw_admission_rejects_total"][
        (("app", "social_media"),)] == 1
    assert parsed["jigsaw_drops_total"][
        (("app", "social_media"), ("reason", "quota"))] == 1


def test_gateway_quota_unknown_app_fails_loud(planned_apps):
    with pytest.raises(ValueError, match="quota for unknown app"):
        AsyncGateway(planned_apps, seed=0, quotas={"nope": 1.0})


def test_gateway_retry_on_drop(planned_apps):
    """retry_drops resubmits the FIRST shed of a hop (deadline budget
    left) instead of failing the root; the second shed is final, and a
    completed retry is counted as a success."""
    hooks = Instrumentation()

    async def drive():
        gw = AsyncGateway(planned_apps, seed=0, hooks=hooks,
                          time_scale=1.0, retry_drops=True)
        app = "social_media"
        g, _ = planned_apps[app]
        qt = f"{app}::{g.entry}"

        # --- first drop: retried, root stays alive ------------------
        gr = await gw.submit(app)
        req = gw.queues[qt].pop()
        now = gw.now()
        retry = gw._drop(req, qt, "staleness", now)
        assert retry is not None and retry.req_id == req.req_id
        assert gr.retries == 1 and gr.dropped == 0
        assert not gr.done.is_set()

        # --- second drop of the same hop: final ---------------------
        final = gw._drop(retry, qt, "staleness", gw.now())
        assert final is None
        assert gr.dropped == 1 and gr.done.is_set()
        assert gr.outcome["status"] == "dropped"
        assert gr.outcome["retries"] == 1 and gr.outcome["retry_ok"] == 0

        # --- retried hop that completes counts a success ------------
        gr2 = await gw.submit(app)
        req2 = gw.queues[qt].pop()
        retry2 = gw._drop(req2, qt, "staleness", gw.now())
        assert retry2 is not None and gr2.retries == 1
        leaf = next(t for t in g.tasks if not g.successors(t))
        srv = gw.by_task[f"{app}::{leaf}"][0]
        gw._complete_hop(retry2, srv, gw.now())
        assert gr2.retry_ok == 1 and gr2.done.is_set()
        assert gr2.outcome["status"] == "ok"
        assert gr2.outcome["retry_ok"] == 1

        # --- past the deadline there is nothing left to retry -------
        gr3 = await gw.submit(app)
        req3 = gw.queues[qt].pop()
        dead = gw._drop(req3, qt, "deadline", req3.deadline + 1.0)
        assert dead is None and gr3.outcome["status"] == "dropped"
        assert gr3.retries == 0

    asyncio.run(drive())
    parsed = parse_exposition(hooks.registry.render())
    assert parsed["jigsaw_gateway_retries_total"][
        (("app", "social_media"),)] == 2
    assert parsed["jigsaw_gateway_retry_success_total"][
        (("app", "social_media"),)] == 1
    # only FINAL sheds count as drops: 2 retried first-sheds excluded
    assert parsed["jigsaw_drops_total"][
        (("app", "social_media"), ("reason", "staleness"))] == 1
    assert parsed["jigsaw_drops_total"][
        (("app", "social_media"), ("reason", "deadline"))] == 1


def test_gateway_unknown_app_fails_loud(planned_apps):
    async def drive():
        gw = AsyncGateway(planned_apps, seed=0)
        with pytest.raises(KeyError, match="unknown app"):
            await gw.submit("nope")

    asyncio.run(drive())


def test_gateway_backend_fault_reaches_caller(planned_apps):
    """A service_s that raises resolves the waiting request with status
    'error', refuses later submits, and stop() re-raises the fault —
    nothing waits forever on a dead dispatcher."""
    class Broken:
        def bind(self, graph, config, app=""):
            pass

        def service_s(self, server, batch, now_s, rng):
            raise RuntimeError("device lost")

        def on_capacity_change(self, servers):
            pass

    async def drive():
        gw = AsyncGateway(planned_apps, Broken(), seed=0, time_scale=0.2)
        await gw.start()
        gr = await gw.submit("social_media")
        await asyncio.wait_for(gr.done.wait(), timeout=10.0)
        with pytest.raises(RuntimeError, match="dispatcher failed"):
            await gw.submit("social_media")
        with pytest.raises(RuntimeError, match="device lost"):
            await gw.stop()
        return gr

    gr = asyncio.run(drive())
    assert gr.outcome["status"] == "error"
    assert "device lost" in gr.outcome["error"]


def test_http_server_smoke(planned_apps):
    """Boot the stdlib HTTP server on an ephemeral port and exercise
    every route over real sockets: healthz, submit (unary + streamed
    NDJSON), /metrics exposition, /trace JSON, /alerts, /audit NDJSON,
    and 404 handling."""
    from repro.obs import AuditLog, SloPlane

    hooks = Instrumentation(tracer=Tracer(), slo=SloPlane(),
                            audit=AuditLog())

    async def fetch(port, method, path, body=b""):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            writer.write(
                f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                f"Content-Length: {len(body)}\r\n"
                f"Connection: close\r\n\r\n".encode() + body)
            await writer.drain()
            raw = await reader.read()
        finally:
            writer.close()
            await writer.wait_closed()
        head, _, payload = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ", 2)[1]), head, payload

    async def drive():
        gw = AsyncGateway(planned_apps, seed=0, hooks=hooks,
                          time_scale=0.2)
        srv = GatewayHTTPServer(gw, hooks, port=0)
        await srv.start()
        try:
            port = srv.port
            status, _, body = await fetch(port, "GET", "/healthz")
            assert status == 200
            health = json.loads(body)
            assert set(health["apps"]) == set(planned_apps)

            # unary submit resolves to the final outcome document
            out = await http_submitter(f"http://127.0.0.1:{port}")(
                "social_media")
            assert out["status"] in ("ok", "dropped")
            assert out["event"] == "done"

            # streamed submit yields NDJSON hop lines ending in done
            status, head, payload = await fetch(
                port, "POST", "/v1/social_media/submit?stream=1")
            assert status == 200
            assert b"chunked" in head.lower()
            lines = [json.loads(ln) for ln in _dechunk(payload).strip()
                     .split(b"\n")]
            assert lines[-1]["event"] == "done"
            assert all(ln["event"] in ("hop", "drop", "done")
                       for ln in lines)

            status, _, body = await fetch(port, "GET", "/metrics")
            assert status == 200
            parsed = parse_exposition(body.decode())
            assert sum(parsed["jigsaw_arrivals_total"].values()) >= 2

            status, _, body = await fetch(port, "GET", "/trace")
            assert status == 200
            validate_chrome_trace(json.loads(body))

            # SLO alert state: rules are listed even when nothing fires
            status, _, body = await fetch(port, "GET", "/alerts")
            assert status == 200
            alerts = json.loads(body)
            assert {r["name"] for r in alerts["rules"]} >= {
                "latency_fast_burn", "latency_slow_burn"}
            assert isinstance(alerts["alerts"], list)

            # flight recorder: NDJSON, every line a well-formed event
            status, head, body = await fetch(port, "GET", "/audit")
            assert status == 200
            assert b"ndjson" in head.lower()
            for ln in body.decode().splitlines():
                ev = json.loads(ln)
                assert {"seq", "t_s", "kind"} <= set(ev)

            status, _, _ = await fetch(port, "GET", "/no/such/route")
            assert status == 404
            status, _, _ = await fetch(port, "POST", "/v1/nope/submit")
            assert status == 404
        finally:
            await srv.stop()

    asyncio.run(drive())


def _dechunk(payload: bytes) -> bytes:
    """Decode an HTTP/1.1 chunked body."""
    out, rest = [], payload
    while rest:
        size_line, _, rest = rest.partition(b"\r\n")
        size = int(size_line, 16)
        if size == 0:
            break
        out.append(rest[:size])
        rest = rest[size + 2:]
    return b"".join(out)
