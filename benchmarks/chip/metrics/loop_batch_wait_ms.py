"""Gateway loop (``gateway/core.py``): wall milliseconds per root in which
the event loop waited with requests queued and nothing else to do (no
generate running, no batch held back): a batch timeout, or servers all
busy. From ``/metrics`` before and after the window:
``jigsaw_gateway_loop_seconds_total{state="batch_wait"}`` over
``jigsaw_arrivals_total``."""

LOOP = 'jigsaw_gateway_loop_seconds_total{state="batch_wait"}'
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
