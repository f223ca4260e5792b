#!/usr/bin/env python3
"""The benchmark: one run of one cell of ``BENCHMARK.json`` on the chips
of the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's ``DecodeServer`` from its configuration and traffic
mix, makes the traffic's data on the device from ``--seed``, warms every
launch shape, then measures for ``--seconds``. With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the JAX profiler and the result carries the per-layer
metrics, the device's busy time and a breakdown. After the window every
sampled window's bits are compared with the plain reference's decode.

The last line of standard output is the result (one JSON object); the
last lines of standard error name each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1."""
import time

T_PROC = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import sys                                               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec
    cell = spec.cell(spec.load_benchmark(), args.workload)
    import jax
    t_jax = time.perf_counter()
    devs = jax.devices()
    t_runtime = time.perf_counter()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from harness import runner, system
    system.use_compile_cache()
    out = runner.run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_setup=t_runtime, devices=devs)
    out["info"]["runtime_start_s"] = {"jax_imported": t_jax - T_PROC,
                                      "chips_found": t_runtime - T_PROC}
    for name, c in out["checks"].items():
        bound = (f"max {c['max']}" if "max" in c else f"min {c['min']}")
        print(f"check {name} {c['value']} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
