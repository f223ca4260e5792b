#!/usr/bin/env python3
"""Where a cell's host time goes, by the program's own spans: traced runs
of a cell with the program's profiler sink installed, and without it, in
one process on the chip.

    python3 bench/trace_spans.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--sink both|on|off]

Each run is the benchmark's ``--trace 1`` run of the cell. With the sink
on, ``repro.obs.ProfilerTracer`` is installed before the server is built,
so every synchronous span of the server (``push``, ``push_sanitize``,
``push_admit``, ``push_stage``, ``push_frame``, ``launch``, ``h2d``,
``retire``, ``retire_wait``, ...) is a ``repro.`` annotation in the
trace, and the trace is read with ``harness.spans`` as well. ``both``
runs each seed twice, sink off and on, in alternating order.

Prints one JSON line per run: the seed, whether the sink was on,
``correct``, the per-layer metrics, breakdown and garbage collections
of the run's result;
with the sink on also each program span's mean microseconds, count,
total and self seconds, the ``span:``/``span longest:`` breakdown
entries, and ``push_parts_pct``, the four ``push_*`` sub-spans' means as
a share of ``push``'s. The last line gives, for each side, the median of
each host-timed per-layer metric over its runs, and the sink's cost on
each as a share of the side without it. Without a TPU it prints nothing
and exits 1."""
import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

PUSH_PARTS = ("push_admit", "push_sanitize", "push_stage", "push_frame")
#: the metrics timed on the host whose cost under the sink is reported
HOST_TIMED = ("push_us", "dispatch_ms", "window_p99_ms", "gen_lag_p99_ms",
              "queue_wait_p99_ms")


def traced_run(cell: str, seed: int, seconds: float, sink: bool,
               devices, **kw) -> dict:
    """One traced run of ``cell``; with ``sink``, under the program's
    profiler sink, its program spans read from the trace."""
    from harness import runner, spans, trace as tracing
    from repro.obs import ProfilerTracer, set_tracer
    planes = []

    def load(path):                    # the run deletes its trace after
        planes.append(spans.load(path))    # reading it: keep the spans
        return planes[-1]

    load_bench = tracing.load
    tracing.load = load
    prev = set_tracer(ProfilerTracer() if sink else None)
    try:
        out = runner.run(cell, seed, seconds, True,
                         t_setup=time.perf_counter(), devices=devices, **kw)
    finally:
        set_tracer(prev)
        tracing.load = load_bench
    row = {"workload": cell, "seed": seed, "sink": sink,
           "correct": out["correct"],
           "metrics": {k: m["value"] for k, m in out["metrics"].items()},
           "breakdown": out.get("breakdown"), "gc": out["info"]["gc"]}
    if sink and planes:
        got = spans.spans(planes[-1]) or {}
        row["spans"] = {k: {"mean_us": 1e6 * s["total_s"] / s["count"],
                            **s} for k, s in sorted(got.items())}
        red = spans.idle(planes[-1])
        if red is not None:
            row["span_idle"] = spans.breakdown(red)
        mean = {k[len(spans.PREFIX):]: s["mean_us"]
                for k, s in row["spans"].items()}
        if mean.get("push"):
            row["push_parts_pct"] = 100 * sum(
                mean.get(p, 0.0) for p in PUSH_PARTS) / mean["push"]
    return row


def summary(rows: list) -> dict:
    """Medians of the host-timed metrics by side, and the sink's cost."""
    out = {}
    for sink in (False, True):
        side = [r["metrics"] for r in rows if r["sink"] is sink]
        out["on" if sink else "off"] = {
            k: statistics.median(m[k] for m in side)
            for k in sorted({k for m in side for k in m})
            if k.split(".")[0] in HOST_TIMED and all(k in m for m in side)}
    if out["on"] and out["off"]:
        out["sink_cost_pct"] = {
            k: 100 * (v / out["off"][k] - 1) for k, v in out["on"].items()
            if out["off"].get(k)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sink", choices=("both", "on", "off"), default="both")
    args = ap.parse_args(argv)

    import jax
    from harness import spec, system
    cell = spec.cell(spec.load_benchmark(), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"trace_spans: needs {cell['chips']} TPU chips, JAX found "
              f"{len(devs)} {devs[0].platform} devices", file=sys.stderr)
        return 1
    system.use_compile_cache()
    sides = {"both": (False, True), "on": (True,), "off": (False,)}
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        order = sides[args.sink][::-1 if i % 2 else 1]
        for sink in order:
            row = traced_run(args.workload, seed, args.seconds, sink, devs)
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "runs": len(rows),
                      "all_correct": all(r["correct"] for r in rows),
                      **summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
