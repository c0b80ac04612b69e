"""Gateway loop (``gateway/core.py``): wall milliseconds per root in which
the event loop held a finished batch's results back (a ``_serve`` sleep
pending, no generate running), from ``/metrics`` before and after the
window: ``jigsaw_gateway_loop_seconds_total{state="replay"}`` over
``jigsaw_arrivals_total``. Per root, so the drain and the trace's
collection after the window do not dilute it."""

LOOP = 'jigsaw_gateway_loop_seconds_total{state="replay"}'
ROOTS = "jigsaw_arrivals_total"


def read(ctx, part):
    w = ctx.window
    if LOOP not in w.metrics_after:
        return None
    roots = sum(v - w.metrics_before.get(k, 0.0)
                for k, v in w.metrics_after.items()
                if k.split("{", 1)[0] == ROOTS)
    secs = w.metrics_after[LOOP] - w.metrics_before.get(LOOP, 0.0)
    return 1e3 * secs / roots if roots > 0 else None
