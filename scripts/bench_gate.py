#!/usr/bin/env python
"""Benchmark-regression gate (scripts/ci.sh).

Runs the kernel sweep + streaming bench + multi-tenant serve bench +
serve-under-faults bench + block-parallel bench + serve load sweep +
tile-plan report, APPENDS the run to BENCH_kernels.json (keeping the
per-PR trajectory), and fails when the best kernel configuration OR the
serve aggregate throughput (clean or under fault injection) OR the
block-parallel throughput regresses more than ``BENCH_GATE_TOL``
(default 20%) against the best comparable run already stored — or when
any serve-load level's p99 latency rises more than the same tolerance
above the best stored p99 (latency gates are inverted: up is bad). Runs
are stamped with the producing platform (trajectory.platform) and only
compared against stored runs of the SAME backend/device kind, so the
interpret-CPU trajectory and any compiled-hardware trajectory gate
independently in one store; ``BENCH_COMPILED=1`` runs the same sections
with compiled kernels on the real backend (exit 0 + notice when the
machine only has a CPU). Timing is min-of-reps, which absorbs most
shared-runner noise; the tolerance absorbs the rest.

  PYTHONPATH=src python scripts/bench_gate.py

Failure modes are explicit, never tracebacks: a corrupt/unreadable
trajectory file, or a benchmark returning an empty/missing section,
prints ``bench gate: ERROR — ...`` and exits 2 (distinct from exit 1 =
a real regression). A missing BENCH_kernels.json is NOT an error — the
run is recorded as the first baseline.

Env knobs: BENCH_GATE_TOL=0.2 (fractional regression allowed),
BENCH_PATH=BENCH_kernels.json, BENCH_COMPILED=1 (compiled-mode gate),
BENCH_PLATFORM=tpu|cpu (force the compiled backend).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


class GateError(Exception):
    """The gate cannot run (bad trajectory file / empty bench section) —
    reported as 'bench gate: ERROR — ...' + exit 2, never a traceback."""


def _load_prior(path: str) -> list[dict]:
    """Stored trajectory runs; [] when the file does not exist yet (first
    run on a fresh checkout is a baseline-recording run, not an error).
    A file that EXISTS but cannot be parsed is an error — silently
    dropping history would let a regression gate itself green."""
    from benchmarks.trajectory import load_runs
    if not os.path.exists(path):
        print(f"bench gate: no trajectory file at {path} — this run "
              f"becomes the first baseline")
        return []
    try:
        runs = load_runs(path)
    except (OSError, ValueError, KeyError, TypeError, AttributeError) as e:
        raise GateError(
            f"trajectory file {path} exists but cannot be read "
            f"({e.__class__.__name__}: {e}); fix or delete it, or point "
            f"BENCH_PATH elsewhere") from None
    if not isinstance(runs, list):
        raise GateError(f"trajectory file {path} parsed to "
                        f"{type(runs).__name__}, expected a list of runs")
    if not runs:
        # a present-but-empty store (fresh {}, empty v2 envelope, bare
        # []) is a first run, same as a missing file — no baseline to
        # gate against, this run records one
        print(f"bench gate: trajectory at {path} holds no prior runs — "
              f"no baseline, recording only")
    return runs


def _section(run: dict, name: str, required_variant: str | None = None):
    """A run section that the gate is about to index into; empty or
    variant-less sections become a clear GateError instead of an
    IndexError/StopIteration."""
    rows = run.get(name)
    if not rows:
        raise GateError(
            f"benchmark produced no '{name}' rows — the {name} bench "
            f"returned empty; the gate cannot compare this run")
    if required_variant is not None:
        row = next((r for r in rows if r.get("variant") == required_variant),
                   None)
        if row is None:
            raise GateError(
                f"'{name}' section has no '{required_variant}' variant row "
                f"(got {sorted({r.get('variant') for r in rows})})")
        return row
    return rows


#: Platform a run without a recorded platform stamp is assumed to be from:
#: every pre-stamp trajectory point was produced by interpret-mode CPU runs.
_LEGACY_PLATFORM = {"backend": "cpu", "device_kind": "cpu"}


def _run_platform(run: dict) -> dict:
    p = run.get("platform") or _LEGACY_PLATFORM
    return {"backend": p.get("backend"), "device_kind": p.get("device_kind")}


def comparable_runs(prior: list[dict], cur_plat: dict,
                    n_bits: int) -> list[dict]:
    """Stored runs the current run may be gated against: same quick (not
    --full) workload with the same kernel-sweep n_bits, AND the same
    platform (backend + device kind) — an interpret-CPU point must never
    be compared to a compiled-GPU/TPU point of the same code (orders of
    magnitude apart), so each platform's trajectory gates independently
    inside one store. Runs without a platform stamp predate the stamp and
    were all produced by interpret-CPU runs (_LEGACY_PLATFORM)."""
    return [r for r in prior
            if not r.get("full")
            and _run_platform(r) == cur_plat
            and all(row.get("n_bits") == n_bits
                    for row in r.get("rows", []))]


def main() -> int:
    from benchmarks.trajectory import (DEFAULT_PATH, append_run, best_mbps,
                                       block_mbps, platform, serve_load_p99,
                                       serve_mbps, serve_under_faults_mbps)

    tol = float(os.environ.get("BENCH_GATE_TOL", "0.2"))
    path = os.environ.get("BENCH_PATH", DEFAULT_PATH)

    prior = _load_prior(path)                  # fail fast, BEFORE the
                                               # heavy imports and the
                                               # minutes-long benches run
    from benchmarks import throughput

    if os.environ.get("BENCH_COMPILED"):
        # compiled-mode gate: same sections, kernels compiled for the real
        # backend; the platform stamp keeps this trajectory separate from
        # the interpret-CPU one, so both gate independently in one store
        from benchmarks import compiled
        backend = compiled.set_platform(os.environ.get("BENCH_PLATFORM"))
        if backend == "cpu":
            print("bench gate: BENCH_COMPILED set but no accelerator is "
                  "available — compiled gate skipped (the interpret-CPU "
                  "gate is the default run)")
            return 0
        throughput.set_compiled(True)
        print(f"bench gate: compiled mode on backend {backend!r}")

    section_s: dict[str, float] = {}

    def timed(name, fn):
        """Run one bench section, keeping its wall time — the recorded
        trajectory then shows where the gate's minutes actually go (and
        when a PR makes one section balloon)."""
        t0 = time.perf_counter()
        out = fn()
        section_s[name] = round(time.perf_counter() - t0, 3)
        return out

    rows = timed("kernels", lambda: throughput.kernel_sweep(full=False))
    stream_rows = timed("streaming",
                        lambda: throughput.streaming_bench(full=False))
    serve_rows = timed("serve", lambda: throughput.serve_bench(full=False))
    faults_rows = timed("serve_faults",
                        lambda: throughput.serve_faults_bench(full=False))
    block_rows = timed("block", lambda: throughput.block_bench(full=False))
    load_rows = timed("serve_load",
                      lambda: throughput.serve_load_sweep(full=False))
    plans = timed("plans", throughput.plan_rows)
    run = {"full": False, "rows": rows, "streaming": stream_rows,
           "serve": serve_rows, "serve_faults": faults_rows,
           "block": block_rows, "serve_load": load_rows, "plans": plans,
           "section_s": section_s, "gate": True}
    if not rows:
        raise GateError("kernel_sweep returned no rows — nothing to gate")
    cur = best_mbps(run)
    n_bits = rows[0]["n_bits"]

    # only compare runs of the same workload size (full flag + n_bits) AND
    # the same platform (backend + device kind): an interpret-CPU point
    # must never be gated against a compiled/TPU point — same code, orders
    # of magnitude apart (pre-stamp legacy runs were all interpret-CPU)
    cur_plat = _run_platform({"platform": platform()})
    comparable = comparable_runs(prior, cur_plat, n_bits)
    skipped_plat = sum(1 for r in prior if _run_platform(r) != cur_plat)
    if skipped_plat:
        print(f"bench gate: ignoring {skipped_plat} stored run(s) from a "
              f"different platform (this run: {cur_plat})")
    append_run(run, path)

    print("bench gate: section wall time — "
          + ", ".join(f"{k} {v:.1f}s" for k, v in section_s.items()))
    single = _section(run, "streaming", "single_shot")
    beststream = max((r["mbps"] for r in stream_rows
                      if r["variant"] != "single_shot"), default=0.0)
    print(f"bench gate: best kernel config {cur:.2f} Mb/s; streaming best "
          f"{beststream:.2f} vs single-shot {single['mbps']:.2f} Mb/s")

    # serve section: aggregate server throughput vs the N-independent
    # baseline of THIS run, and vs stored server runs of the same workload
    srv = serve_mbps(run)
    indep = serve_mbps(run, "independent")
    srow = _section(run, "serve", "server")
    print(f"bench gate: serve {srow['sessions']} sessions/"
          f"{srow['buckets']} buckets — server {srv:.2f} Mb/s vs "
          f"independent {indep:.2f} Mb/s (occupancy "
          f"{srow['occupancy']:.2f}, p99 {srow['p99_ms']:.1f} ms, "
          f"{srow['plan_traces']} compiles)")
    if srv < indep:
        print("bench gate: WARNING — server below summed independent "
              "StreamDecoders this run (runner noise?); see the stored "
              "trajectory for the trend")
    fail = []
    serve_comp = [serve_mbps(r) for r in comparable
                  if any(row.get("variant") == "server"
                         and row.get("sessions") == srow["sessions"]
                         and row.get("n_bits") == srow["n_bits"]
                         for row in r.get("serve", []))]
    if serve_comp:
        sbase = max(serve_comp)
        print(f"bench gate: stored serve baseline {sbase:.2f} Mb/s "
              f"(floor {(1 - tol) * sbase:.2f})")
        if srv < (1.0 - tol) * sbase:
            fail.append(f"serve aggregate regressed "
                        f"{(1 - srv / sbase):.0%} (> {tol:.0%})")
    else:
        print("bench gate: no comparable stored serve baseline — "
              "recorded only")

    # serve-under-faults section: the same comparison for the workload
    # with the seeded 1%-launch-failure injection — catches a fault-
    # tolerance layer whose recovery path got expensive
    frow = _section(run, "serve_faults", "server_faults")
    fsrv = serve_under_faults_mbps(run)
    print(f"bench gate: serve under faults {fsrv:.2f} Mb/s "
          f"({frow['injected']} injected launch failures, "
          f"{frow['retries']} retries, {frow['degraded']} degraded, "
          f"health={frow['health']})")
    faults_comp = [serve_under_faults_mbps(r) for r in comparable
                   if any(row.get("variant") == "server_faults"
                          and row.get("sessions") == frow["sessions"]
                          and row.get("n_bits") == frow["n_bits"]
                          for row in r.get("serve_faults", []))]
    if faults_comp:
        fbase = max(faults_comp)
        print(f"bench gate: stored serve-under-faults baseline "
              f"{fbase:.2f} Mb/s (floor {(1 - tol) * fbase:.2f})")
        if fsrv < (1.0 - tol) * fbase:
            fail.append(f"serve-under-faults aggregate regressed "
                        f"{(1 - fsrv / fbase):.0%} (> {tol:.0%})")
    else:
        print("bench gate: no comparable stored serve-under-faults "
              "baseline — recorded only")

    # block section: intra-frame block-parallel vs sequential-scan plan on
    # the long-frame workload; block_bench already asserts the >= 1.5x
    # acceptance ratio, the gate additionally tracks the blocked Mb/s
    # trajectory like the serve sections
    brow = _section(run, "block", "blocked")
    blk = block_mbps(run)
    seq = block_mbps(run, "sequential")
    print(f"bench gate: block f={brow['f']} x{brow['block_frames']} "
          f"(overlap {brow['overlap']}) — blocked {blk:.2f} Mb/s vs "
          f"sequential {seq:.2f} Mb/s ({blk / seq:.1f}x)")
    block_comp = [block_mbps(r) for r in comparable
                  if any(row.get("variant") == "blocked"
                         and row.get("n_bits") == brow["n_bits"]
                         for row in r.get("block", []))]
    if block_comp:
        bbase = max(block_comp)
        print(f"bench gate: stored block baseline {bbase:.2f} Mb/s "
              f"(floor {(1 - tol) * bbase:.2f})")
        if blk < (1.0 - tol) * bbase:
            fail.append(f"block-parallel throughput regressed "
                        f"{(1 - blk / bbase):.0%} (> {tol:.0%})")
    else:
        print("bench gate: no comparable stored block baseline — "
              "recorded only")

    # serve_load section: tail-latency-under-load SLO curves. INVERTED
    # semantics vs every section above — p99 latency regresses UP, so each
    # offered-load level fails when its p99 exceeds (1 + tol) x the best
    # (minimum) stored comparable p99 at that level
    lrows = _section(run, "serve_load")
    print("bench gate: serve load sweep — "
          + ", ".join(f"{r['sessions']} sess: p99 {r['p99_ms']:.1f} ms "
                      f"(queue {r['queue_p99_ms']:.1f})" for r in lrows))
    for lrow in lrows:
        lvl, cur_p99 = lrow["sessions"], lrow["p99_ms"]
        load_comp = [serve_load_p99(r, lvl) for r in comparable
                     if any(row.get("sessions") == lvl
                            and row.get("n_bits") == lrow["n_bits"]
                            for row in r.get("serve_load", []))]
        load_comp = [p for p in load_comp if p > 0]
        if not load_comp:
            print(f"bench gate: no stored serve-load baseline at {lvl} "
                  f"sessions — recorded only")
            continue
        lbase = min(load_comp)
        ceil = (1.0 + tol) * lbase
        print(f"bench gate: stored serve-load p99 baseline at {lvl} "
              f"sessions {lbase:.1f} ms (ceiling {ceil:.1f})")
        if cur_p99 > ceil:
            fail.append(f"serve p99 at {lvl} sessions regressed "
                        f"{(cur_p99 / lbase - 1):.0%} (> {tol:.0%})")

    if not comparable:
        print("bench gate: no comparable stored baseline — recorded only")
        return 1 if fail else 0
    base = max(best_mbps(r) for r in comparable)
    floor = (1.0 - tol) * base
    print(f"bench gate: stored baseline best {base:.2f} Mb/s "
          f"(floor {floor:.2f}, tol {tol:.0%})")
    if cur < floor:
        fail.append(f"best kernel config regressed "
                    f"{(1 - cur / base):.0%} (> {tol:.0%})")
    for msg in fail:
        print(f"bench gate: FAIL — {msg} vs stored baseline")
    if fail:
        return 1
    print("bench gate: OK")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except GateError as e:
        print(f"bench gate: ERROR — {e}")
        sys.exit(2)
