#!/usr/bin/env python3
"""The knee of an open-loop cell: its traffic at a rising number of links
(calls), each at the cell's link rate, on several seeds, in one process
on the chip.

    python3 bench/sweep_calls.py --workload gsm_tchfs.calls \
        --links 100,200,400,800 --seeds 1,2,3 --seconds 10 [--slots 64]

For each count and seed prints one JSON line: window_p50_ms,
window_p99_ms, the generator's lag p99, windows still outstanding when
the window closed, windows per launch, the largest garbage-collection
pause, and whether every window was correct. The last line names two
knees, each the most calls at which every seed held:

  knee_p99      window_p99_ms and the generator's lag p99 within one
                push period (a decoder slower than that falls behind the
                call);
  knee_backlog  window_p50_ms within one push period and launches below
                ``slots`` windows on average: the backlog does not grow.

and four fifths of each, the count a cell below the knee runs."""
import time

T_PROC = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import sys                                               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--links", required=True, help="comma-separated counts")
    ap.add_argument("--seeds", default="1", help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--slots", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from harness import runner, spec, system
    from harness.traffic import Traffic
    bm = spec.load_benchmark()
    cell = spec.cell(bm, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"sweep: needs {cell['chips']} TPU chips, JAX found "
              f"{len(devs)} {devs[0].platform} devices", file=sys.stderr)
        return 1
    system.use_compile_cache()
    cfg = spec.load_config(cell["config"])
    period_ms = Traffic.from_json(spec.load_traffic(cell["traffic"])) \
        .period_s(cfg) * 1e3
    pct = (lambda a, q: float(np.percentile(a, q)) if a.size else None)
    held = {"knee_p99": {}, "knee_backlog": {}}
    slots = None
    for n in (int(x) for x in args.links.split(",")):
        over = {"links": n}
        if args.slots:
            over["slots"] = args.slots
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            out, rec = runner.execute(args.workload, seed, args.seconds,
                                      False, t_setup=t0, devices=devs,
                                      traffic_over=over)
            slots = rec.traffic.slots
            row = {
                "links": n, "seed": seed, "slots": slots,
                "window_p50_ms": pct(rec.lat_ms, 50),
                "window_p99_ms": pct(rec.lat_ms, 99),
                "gen_lag_p99_ms": pct(rec.lag_ms, 99),
                "backlog_end": out["info"]["backlog_end"],
                "windows_per_launch": rec.delta["windows"]
                / max(1, rec.delta["launches"]),
                "push_us": pct(rec.push_us, 50),
                "gc_max_ms": out["info"]["gc"]["max_ms"],
                "attempted": out["attempted"], "correct": out["correct"],
                "run_s": time.perf_counter() - t0}
            print(json.dumps(row), flush=True)
            done = row["window_p50_ms"] is not None
            ok = {"knee_p99": done and row["window_p99_ms"] <= period_ms
                  and row["gen_lag_p99_ms"] <= period_ms,
                  "knee_backlog": done and row["window_p50_ms"] <= period_ms
                  and row["windows_per_launch"] < slots}
            for rule, good in ok.items():
                held[rule][n] = held[rule].get(n, True) and good
    summary = {"workload": args.workload, "period_ms": period_ms,
               "held": held}
    for rule, by_n in held.items():
        knee = max((n for n, good in by_n.items() if good), default=None)
        summary[rule] = knee
        summary[f"{rule}_four_fifths"] = knee and knee * 4 // 5
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
