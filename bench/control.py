#!/usr/bin/env python3
"""Readings of the correctness check's lower and upper ends, on the chip,
at a cell's own size, for several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed the cell runs once, and the check is made twice on what
its window served:

  sound     the served bits against the float32 reference, as every run
            of the benchmark checks them;
  control   the reference computed in bfloat16 (branch and path metrics)
            put in the program's place: its bits at the same sampled
            windows go through the same comparison and the same limits.

Prints one JSON line per seed (each side's verdict and its
``bit_mismatches``), then a summary with the largest sound reading, the
smallest control reading and whether any control run came out correct.
Without a TPU it prints nothing and exits 1."""
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
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import jax
    import ml_dtypes
    from harness import runner, spec, system
    cell = spec.cell(spec.load_benchmark(), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"control: needs {cell['chips']} TPU chips, JAX found "
              f"{len(devs)} {devs[0].platform} devices", file=sys.stderr)
        return 1
    system.use_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = runner.run(args.workload, seed, args.seconds, False,
                         t_setup=time.perf_counter(), devices=devs,
                         control_dtype=ml_dtypes.bfloat16)
        ctl = out["control"]
        row = {"seed": seed, "correct": out["correct"],
               "sound": out["checks"]["bit_mismatches"]["value"],
               "control_correct": ctl["correct"],
               "control": ctl["checks"]["bit_mismatches"]["value"],
               "bits_compared": out["checks"]["bits_compared"]["value"],
               "bit_errors": out["info"]["bit_errors"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "sound_max": max(r["sound"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "all_sound_correct": all(r["correct"] for r in rows),
        "any_control_correct": any(r["control_correct"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
