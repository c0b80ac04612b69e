"""Readings that set the limits of ``correct``: the program's widest gap
and the control's, on many seeds, in one process.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds <n>[,<n>...] [--seconds <s>] [--quants int8,fp8]

The benchmark's own runs never run this. For each seed it does what a run
does at the cell's own size and load: load the seed's weights into the
deployment (built once, then reused), serve a short open-loop window, free
the weights, and run the reference over a sample of what was served. It
then puts the reference in a lower precision in the program's place
(the family's ``logits`` with ``quant``) and reads, at each position of the
same prompts and served tokens, the gap of the token that it ranks first.
Each reading is judged as a run judges its own (``harness.judge``, with
the configuration's ``limits``): the program has to come out ``correct``
and each control not. One JSON line per seed: ``program`` and
``control`` per precision, each with its gap numbers
(``correct.gap_numbers``, per model), the numbers compared beside their
limits, and ``correct``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def judged(cell, gaps, compiles_in_window):
    """Gap numbers, the numbers compared beside their limits, and
    ``correct``, as a run of ``cell`` would judge them."""
    from benchmarks.chip import harness as H
    numbers, ok = H.judge(cell.config["limits"], gaps, compiles_in_window)
    return {"gaps": gaps, "checks": numbers, "correct": ok}


def readings(cell, dims, seeds, seconds, quants, log):
    """Yield one dict of readings per seed."""
    from benchmarks.chip import correct as C
    from benchmarks.chip import harness as H
    from benchmarks.chip import weights as W
    backend = None
    rows = int(cell.config["sample_rows"])
    prompt_len = int(cell.traffic["prompt_len"])
    for seed in seeds:
        dep = H.deploy(cell, dims, seed, backend)
        backend = dep.backend
        backend.calls.clear()
        w = H.serve_window(dep, seed, seconds, trace=False)
        calls = list(backend.calls)
        backend.release()
        words = W.seed_words(seed)
        gaps = {"program": {}, **{q: {} for q in quants}}
        out = {"seed": seed, "roots": len(w.roots), "failed": H.failed(w),
               "compiles_in_window": w.compiles.n, "rows": {}}
        for arch in sorted({a for a, _, _ in calls}):
            prompts, served = C.sample_rows(calls, arch, seed, rows)
            ref = C.reference_logits(dims[arch], words, prompts, served)
            out["rows"][arch] = len(prompts)
            gaps["program"].update(C.gap_numbers(
                C.served_gaps(ref, served), arch))
            for q in quants:
                ctl = C.reference_logits(dims[arch], words, prompts, served,
                                         quant=q)
                gaps[q].update(C.gap_numbers(C.control_gaps(ref, ctl), arch))
        out["program"] = judged(cell, gaps["program"], w.compiles.n)
        # the control stands in for the program on the same served rows;
        # the window's compiles are the program's
        out["control"] = {q: judged(cell, gaps[q], 0) for q in quants}
        log(f"seed {seed}: {json.dumps(out)}")
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per seed; default: long enough that each "
                    "model serves the rows a run compares")
    ap.add_argument("--quants", default="int8,fp8")
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
    seconds = args.seconds or (
        float(cell.config["sample_rows"]) / cell.rate_rps + 5.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    quants = [q for q in args.quants.split(",") if q]
    t0 = time.monotonic()
    with H.registered(cell) as dims:
        outs = list(readings(cell, dims, seeds, seconds, quants,
                             lambda m: print(m, flush=True)))
    summary = {"workload": cell.name, "seconds": seconds,
               "wall_s": time.monotonic() - t0,
               "program_max": {}, "control_min": {},
               "program_correct": [o["program"]["correct"] for o in outs],
               "control_correct": {q: [o["control"][q]["correct"]
                                       for o in outs] for q in quants}}
    for o in outs:
        for a, g in o["program"]["gaps"].items():
            summary["program_max"][a] = max(
                summary["program_max"].get(a, 0.0), g)
        for q in quants:
            for a, g in o["control"][q]["gaps"].items():
                key = f"{q}.{a}"
                summary["control_min"][key] = min(
                    summary["control_min"].get(key, float("inf")), g)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
