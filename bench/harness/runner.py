"""One run of one cell: data from the seed, the system built and warmed,
the measured window, the drain, the comparison with the reference, and
the result line's fields. ``run.py`` calls ``run`` after it has found
the chips; tests call it on the CPU with smaller traffic."""
from __future__ import annotations

import gc
import shutil
import tempfile
import types

import numpy as np

from . import channel, codes, drive, spec, system, trace as tracing
from .traffic import Schedule, Traffic

#: bits of returned windows kept for the comparison (a reservoir sample
#: of the windows polled from the window on)
SAMPLE_BITS = 1 << 26
#: closed loop: rounds run before the window (every shape launched)
WARM_ROUNDS = 4
#: the fault counters of the server that must stay 0
FAULTS = ("launch_errors", "timeouts", "retries", "degraded",
          "breaker_trips", "evacuated", "poisoned_pushes", "quarantined")


def _delta(a: dict, b: dict) -> dict:
    out = {k: b[k] - a[k] for k in a if k not in ("stages",)}
    out["stages"] = {
        name: {"counts": [y - x for x, y in zip(sa["counts"],
                                                b["stages"][name]["counts"])],
               "bounds": sa["bounds"],
               "count": b["stages"][name]["count"] - sa["count"],
               "total": b["stages"][name]["total"] - sa["total"]}
        for name, sa in a["stages"].items()}
    return out


#: rows of sampled windows compared at a time (bounds the index arrays)
COMPARE_BITS = 1 << 22


def _blocks(link, start, wb: int):
    """(rows, stream positions (rows, wb)) of the sample, block by block."""
    step = max(1, COMPARE_BITS // wb)
    for i in range(0, link.size, step):
        rows = slice(i, i + step)
        yield rows, start[rows, None] + np.arange(wb)


def _expected(ref, link: np.ndarray, pos: np.ndarray, f: int) -> np.ndarray:
    """A decode's bits at stream positions ``pos`` (rows, wb) of the rows'
    links, from its (first, steady) pair (see
    ``references/framed_viterbi.expected``)."""
    first, steady = ref
    links = np.broadcast_to(link[:, None], pos.shape)
    want = steady[links, pos % steady.shape[1]]
    head = pos < f
    want[head] = first[links[head], pos[head]]
    return want


def decode_at(ref, link, start, wb: int, f: int) -> np.ndarray:
    """A decode's bits at the sampled windows: what it would have served
    in the program's place."""
    out = np.empty((link.size, wb), np.int8)
    for rows, pos in _blocks(link, start, wb):
        out[rows] = _expected(ref, link[rows], pos, f)
    return out


def compare(sample, ref, sent, f: int) -> dict:
    """Bits of the sampled windows that differ from the reference's decode
    of the same stream positions, and (for information) from the sent
    bits."""
    link, start, bits = sample
    wb = bits.shape[1]
    bad = wrong = 0
    for rows, pos in _blocks(link, start, wb):
        got = bits[rows]
        bad += int(np.count_nonzero(got != _expected(ref, link[rows], pos,
                                                     f)))
        wrong += int(np.count_nonzero(
            got != sent[link[rows, None], pos % sent.shape[1]]))
    return {"bits_compared": int(bits.size), "bit_mismatches": bad,
            "bit_errors": wrong}


def checks_of(cmp: dict, missing: int, counters: dict,
              anomalies: int) -> dict:
    """Each number the check compares, with its limit."""
    faults = sum(counters[c] for c in FAULTS)
    return {
        "bit_mismatches": {"value": cmp["bit_mismatches"], "max": 0},
        "windows_missing": {"value": missing, "max": 0},
        "fallback_launches": {"value": counters["degraded"], "max": 0},
        "server_faults": {"value": faults, "max": 0},
        "stray_polls": {"value": anomalies, "max": 0},
        "bits_compared": {"value": cmp["bits_compared"], "min": 1},
    }


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["max"] if "max" in c
               else c["value"] >= c["min"] for c in checks.values())


def run(*args, **kwargs) -> dict:
    """Run a cell; returns the result line's fields, ``checks`` last."""
    return execute(*args, **kwargs)[0]


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t_setup: float, devices, traffic_over: dict | None = None,
            control_dtype=None, bench=spec.BENCH, root=spec.ROOT):
    """Run a cell; returns (the result line's fields plus ``checks``,
    the record its metrics were read from).

    ``traffic_over`` replaces traffic parameters (tests run small).
    ``control_dtype`` adds ``control``: the check's verdict and numbers
    when the reference computed in that dtype is put in the program's
    place, its bits at the sampled windows compared as the served ones
    are (the control of the correctness check)."""
    import jax
    bm = spec.load_benchmark(root)
    cell = spec.cell(bm, cell_name)
    cfg = spec.load_config(cell["config"], bench)
    tr = Traffic.from_json({**spec.load_traffic(cell["traffic"], bench),
                            **(traffic_over or {})})
    devices = list(devices)[:cell["chips"]]
    f, v2 = cfg["frame"]["f"], cfg["frame"]["v2"]
    pool_stages = tr.pool_stages(cfg)

    parts = {"start": drive.clock() - t_setup}
    sent, rx = channel.make_pool(seed, cfg, tr.links, tr.pool_pushes,
                                 tr.push_stages(cfg), device=devices[0])
    parts["data"] = drive.clock() - t_setup
    srv, sids, busy = system.build(cfg, tr, devices)
    per = tr.push_symbols(cfg)
    links = [drive.Link(i, sid, [rx[i, j * per:(j + 1) * per]
                                 for j in range(tr.pool_pushes)],
                        tr.push_stages(cfg), tr.window_bits(cfg), v2)
             for i, sid in enumerate(sids)]
    rec = drive.Recorder(seed, SAMPLE_BITS, tr.window_bits(cfg))
    inf = float("inf")
    parts["built"] = drive.clock() - t_setup

    # warm every launch shape the window will use
    if tr.loop == "closed":
        drive.closed_loop(srv, links, busy, rec, until=inf, w0=inf, w1=inf,
                          iterations=WARM_ROUNDS)
    else:
        drive.warm_open(srv, links, busy, rec, tr.slots)
    parts["warmed"] = drive.clock() - t_setup
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        tracing.start(log_dir)
    ann = drive.annotator(trace)
    gcw = drive.GcWatch()

    if tr.loop == "closed":
        w0 = drive.clock()
        w1 = w0 + seconds
        c0 = system.counters(srv)
        with ann(tracing.WINDOW), gcw:
            drive.closed_loop(srv, links, busy, rec, until=w1, w0=w0, w1=w1,
                              trace=trace)
    else:
        t_s = drive.clock() + 0.01
        w0 = t_s + tr.warm_s
        w1 = w0 + seconds
        loop = drive.OpenLoop(srv, links, busy, rec,
                              Schedule(tr, cfg, seed, t_s), slots=tr.slots,
                              w0=w0, w1=w1, trace=trace)
        loop.run(until=w0)
        c0 = system.counters(srv)
        with ann(tracing.WINDOW), gcw:
            loop.run(until=w1)
    backlog = sum(len(ln.pending) for ln in links)
    c1 = system.counters(srv)
    if trace:
        jax.profiler.stop_trace()
    if tr.loop == "open":
        loop.catch_up()
    drive.drain(srv, links, rec)
    c2 = system.counters(srv)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    missing = sum(1 for ln in links for _, counted in ln.pending if counted)
    del srv, links
    gc.collect()

    red = None
    if log_dir is not None:
        path = tracing.find_xplane(log_dir)
        red = tracing.reduce(tracing.load(path)) if path else None
        shutil.rmtree(log_dir, ignore_errors=True)

    k, polys = codes.generators(cfg)
    ref = spec.reference(cfg["reference"], bench)
    mask = codes.puncture_mask(cfg)
    want = ref.expected(rx, k, polys, mask, cfg["frame"], pool_stages)
    sample = rec.sample()
    cmp = compare(sample, want, sent, f)
    checks = checks_of(cmp, missing, c2, len(rec.anomalies))
    correct = passes(checks)
    control = None
    if control_dtype is not None:
        low = ref.expected(rx, k, polys, mask, cfg["frame"], pool_stages,
                           dtype=control_dtype)
        link, start, bits = sample
        served = (link, start, decode_at(low, link, start, bits.shape[1], f))
        ctl = checks_of(compare(served, want, sent, f), missing, c2,
                        len(rec.anomalies))
        control = {"correct": passes(ctl), "checks": ctl}
    faults = {c: c2[c] for c in FAULTS}

    record = types.SimpleNamespace(
        cfg=cfg, traffic=tr, window_s=seconds, setup_s=w0 - t_setup,
        bits_in_window=rec.bits_in_window,
        lat_ms=np.asarray(rec.lat_s) * 1e3,
        push_us=np.asarray(rec.push_s) * 1e6,
        lag_ms=np.asarray(rec.lag_s) * 1e3,
        delta=_delta(c0, c1), trace=red,
        device_kind=devices[0].device_kind, chips=len(devices))
    metrics = {}
    for m in spec.metrics_for(bm, cell_name, trace):
        value = spec.reader(m["name"], bench)(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(rec.attempted),
           "failed": int(missing + c2["degraded"] + len(rec.anomalies)),
           "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = tracing.breakdown(red)
    out["info"] = {"bit_errors": cmp["bit_errors"],
                   "refused_pushes": rec.refused, "backlog_end": backlog,
                   "setup_s_at": parts, "gc": gcw.summary(),
                   "faults": faults, "anomalies": rec.anomalies[:5]}
    if control is not None:
        out["control"] = control
    out["checks"] = checks
    return out, record
