"""Find a configuration's knee: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2,3,4 \\
        --seeds <n>,<n> [--seconds 51]

The benchmark's own runs never run this; its finding is written into the
configuration file as ``knee_rps``. One process builds the engines once;
each rate is planned at that rate, as a cell would be, and served for one
open-loop window per seed. One JSON line per rate and seed, then one line
per rate with the verdict.

A rate is sustained when, on every seed, no root fails and the backlog
does not grow:

- every root comes back ok before the drain limit: none is dropped,
  refused, errored or left undrained;
- the generator does not fall behind: the median lateness of the sends of
  the window's last third of roots is at most ``LATE_GROWTH_MS`` above
  that of its first third;
- answers do not fall behind: the median latency of the roots served ok
  in the last third is at most ``LATENCY_GROWTH`` times that of the
  first third.

The knee is the highest swept rate that is sustained, with every lower
swept rate sustained too. The share of roots that came back ok within the
configuration's SLO is printed beside it, and is not part of the rule.
"""
import argparse
import dataclasses
import json
import math
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

LATE_GROWTH_MS = 100.0
LATENCY_GROWTH = 1.10


def thirds(w) -> dict:
    """Median lateness of sends and median latency of ok roots in the
    window's first and last thirds of roots, by due time."""
    k = max(len(w.roots) // 3, 1)
    out = {}
    for name, part in (("first", w.roots[:k]), ("last", w.roots[-k:])):
        late = [r.late_ms for r in part if not math.isnan(r.sent_s)]
        ok = [r.latency_ms for r in part if r.status == "ok"]
        out[f"{name}_third_late_p50_ms"] = (float(np.median(late)) if late
                                            else math.inf)
        out[f"{name}_third_ok_p50_ms"] = (float(np.median(ok)) if ok
                                          else math.inf)
    return out


def sustained(line: dict) -> bool:
    return (set(line["statuses"]) == {"ok"}
            and line["last_third_late_p50_ms"]
            <= line["first_third_late_p50_ms"] + LATE_GROWTH_MS
            and line["last_third_ok_p50_ms"]
            <= LATENCY_GROWTH * line["first_third_ok_p50_ms"])


def knee(verdicts) -> float:
    """The highest rate sustained with every lower rate sustained;
    ``verdicts`` is ``[(rate, sustained)]``; 0 when the lowest is not."""
    best = 0.0
    for rate, ok in sorted(verdicts):
        if not ok:
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    if sys.path and Path(sys.path[0]).resolve() == HERE:
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness as H

    cell = H.load_cell(ROOT, args.workload)
    dev = H.device_info()
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print("error: needs a TPU", file=sys.stderr)
        return 2
    H.enable_compile_cache(ROOT)
    tr = cell.traffic
    slo = float(cell.config["slo_ms"])
    drain_s = float(tr["drain_s"])
    seeds = [int(s) for s in args.seeds.split(",")]
    backend = None
    verdicts = []
    with H.registered(cell) as dims:
        for rate in (float(r) for r in args.rates.split(",")):
            at = dataclasses.replace(
                cell, config={**cell.config, "knee_rps": rate},
                traffic={**tr, "rate_of_knee": 1.0})
            ok_all = True
            for seed in seeds:
                dep = H.deploy(at, dims, seed, backend)
                backend = dep.backend
                backend.calls.clear()
                w = H.serve_window(dep, seed, args.seconds, trace=False)
                e2e = H.end_to_end(w, slo, drain_s, math.nan)
                late = [r.late_ms for r in w.roots
                        if not math.isnan(r.sent_s)]
                line = {
                    "rate_rps": rate, "seed": seed, "roots": len(w.roots),
                    "servers": dep.servers(),
                    "attainment": e2e["goodput_rps"] * w.seconds
                    / len(w.roots),
                    "served_share": e2e["served_share"],
                    "statuses": H.statuses(w),
                    "hop_drops": H.drops_by_reason(w),
                    "p50_ms": e2e["latency_p50_ms"],
                    "p90_ok_ms": e2e["latency_p90_ms"],
                    **thirds(w),
                    "late_ms_max": max(late) if late else math.inf,
                    "compiles_in_window": w.compiles.n,
                }
                line["sustained"] = sustained(line)
                ok_all = ok_all and line["sustained"]
                print(json.dumps(line), flush=True)
            verdicts.append((rate, ok_all))
            print(json.dumps({"rate_rps": rate, "sustained": ok_all}),
                  flush=True)
    print(json.dumps({"knee_rps": knee(verdicts)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
