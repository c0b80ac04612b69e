"""Engine (``serving/engine.py``): host milliseconds per ``generate`` spent
outside the token pulls, dispatching prefill, decode and argmax, from
``/metrics`` before and after the window:
``jigsaw_engine_seconds_total{phase="host"}`` over
``jigsaw_engine_generates_total``. ``generate_host_ms.<arch>`` keeps one
model's engine."""

SECONDS = "jigsaw_engine_seconds_total"
GENERATES = "jigsaw_engine_generates_total"


def _delta(w, family, part, phase=None):
    """Summed change of ``family``'s samples, of arch ``part`` if given
    and of ``phase`` if given; None where the family is not exposed."""
    keys = [k for k in w.metrics_after if k.split("{", 1)[0] == family
            and (part is None or f'arch="{part}"' in k)
            and (phase is None or f'phase="{phase}"' in k)]
    if not keys:
        return None
    return sum(w.metrics_after[k] - w.metrics_before.get(k, 0.0)
               for k in keys)


def read(ctx, part):
    host = _delta(ctx.window, SECONDS, part, "host")
    n = _delta(ctx.window, GENERATES, part)
    if host is None or not n:
        return None
    return 1e3 * host / n
