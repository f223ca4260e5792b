"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's natural
metric: Mb/s for throughput tables, dB-to-theory for BER tables).

  PYTHONPATH=src python -m benchmarks.run [--full] [--only SECTION]

The ``kernels`` section additionally persists its rows to
``BENCH_kernels.json`` (cwd) — the perf-trajectory datapoint for the
survivor-compression work; diff it across PRs.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized grids (slow)")
    ap.add_argument("--only", default=None,
                    choices=["throughput", "kernels", "ber"])
    args = ap.parse_args()

    from . import ber_tables, throughput

    print("name,us_per_call,derived")
    if args.only in (None, "kernels"):
        from .trajectory import append_run
        rows = throughput.kernel_sweep(full=args.full)
        for r in rows:
            name = (f"kern_pack{int(r['pack'])}_radix{r['radix']}_"
                    f"ft{r['ft']}" + ("_auto" if r["auto"] else ""))
            print(f"{name},{r['us_per_call']:.1f},{r['mbps']:.2f}Mbps")
        stream_rows = throughput.streaming_bench(full=args.full)
        for r in stream_rows:
            print(f"stream_{r['variant']},{r['us_per_call']:.1f},"
                  f"{r['mbps']:.2f}Mbps")
        serve_rows = throughput.serve_bench(full=args.full)
        for r in serve_rows:
            print(f"serve_{r['variant']}_s{r['sessions']},"
                  f"{r['us_per_call']:.1f},{r['mbps']:.2f}Mbps")
        plans = throughput.plan_rows()
        for r in plans:
            print(f"plan_{r['plan']},0,ft{r['ft']}@{r['vmem_kib']}KiB")
        # workload metadata: cross-PR diffs are only meaningful when
        # these match (sweep timing reps live in throughput.kernel_sweep);
        # runs APPEND to BENCH_kernels.json — the per-PR trajectory the
        # regression gate (scripts/bench_gate.py) checks against.
        append_run({"full": args.full, "rows": rows,
                    "streaming": stream_rows, "serve": serve_rows,
                    "plans": plans})
    if args.only in (None, "throughput"):
        for r in throughput.main(full=args.full):
            name = f"tput_{r['table']}_" + "_".join(
                f"{k}{v}" for k, v in r.items()
                if k in ("f", "v2", "f0", "variant"))
            print(f"{name},{r['us_per_call']:.1f},{r['mbps']:.2f}Mbps")
    if args.only in (None, "ber"):
        for r in ber_tables.main(full=args.full):
            name = f"ber_{r['table']}_" + "_".join(
                f"{k}{v}" for k, v in r.items()
                if k in ("f", "v2", "f0", "start"))
            print(f"{name},0,{r['dist_db']:.3f}dB")


if __name__ == "__main__":
    main()
