"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``load`` turns it into planes
of named lines of events (name, start and end in ns). On a TPU a device
operation's name is its HLO instruction (``%name = type[shape]{layout}
op(...)``), on the same clock as the host's events. ``reduce`` takes the
traced
window from the host annotation ``bench.window``, and for each device
plane: the union of its operations' intervals (busy time), time per
operation name, time of each named kernel, and how the idle gaps fall
against the host annotations the loop makes (``bench.push``, ``.step``,
``.poll``, ``.wait``)."""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the kernels whose device time the metrics read, by the name a metric
#: uses: matched against each device operation's name. On the chip the unified Viterbi kernel's Pallas call shows as
#: ``%unified_decode_frames.1 = ... custom_call_target="tpu_custom_call"``.
KERNELS = {"viterbi_unified": re.compile(
    r"^%?unified_decode_frames[.\d]* = .*tpu_custom_call")}
WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict         # line name -> [Event]


def start(log_dir: str) -> None:
    """Start the profiler with the Python function tracer off: the host
    keeps its runtime events and the benchmark's own annotations."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str | None:
    hits = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load(path: str) -> list[Plane]:
    """Planes of an ``.xplane.pb``: every event of a device's operations
    line, and the host's ``bench.`` annotations."""
    from jax.profiler import ProfileData
    planes = []
    for p in ProfileData.from_file(path).planes:
        device = p.name.startswith(DEVICE_PREFIX)
        lines = {}
        for ln in p.lines:
            keep = device and ln.name == OPS_LINE
            lines[ln.name] = [Event(e.name, int(e.start_ns), int(e.end_ns))
                              for e in ln.events
                              if keep or e.name.startswith(HOST_PREFIX)]
        planes.append(Plane(p.name, lines))
    return planes


_HLO = re.compile(r"^%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``%copy.3 = s32[64,189]{1,0:T(8,128)} copy(...)`` -> ``copy.3
    copy s32[64,189]``; other names as they are."""
    m = _HLO.match(name)
    return f"{m[1]} {m[4]} {m[2]}[{m[3]}]" if m else name


def _union(intervals):
    """Merged, sorted disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _gaps(busy, w0, w1):
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    return gaps


def _attribute(gaps, host):
    """(ns of the gaps under each host annotation, [(ns, label)] of each
    gap with the annotation that covers most of it). Annotations are
    disjoint sorted intervals; time under none is 'host:other'."""
    out, each = {}, []
    j = 0
    for a, b in gaps:
        parts = {}
        while j < len(host) and host[j][1] <= a:
            j += 1
        k = j
        while k < len(host) and host[k][0] < b:
            lo, hi = max(a, host[k][0]), min(b, host[k][1])
            if hi > lo:
                parts[host[k][2]] = parts.get(host[k][2], 0) + (hi - lo)
            k += 1
        rest = (b - a) - sum(parts.values())
        if rest > 0:
            parts["host:other"] = rest
        for name, ns in parts.items():
            out[name] = out.get(name, 0) + ns
        each.append((b - a, max(parts, key=parts.get)))
    return out, each


def reduce(planes: list[Plane]) -> dict | None:
    """The trace's numbers over the window, or None when the trace has no
    window annotation or no device plane."""
    host, window = [], None
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for evs in p.lines.values():
            for e in evs:
                if e.name == WINDOW:
                    window = (e.start, e.end)
                elif e.name.startswith(HOST_PREFIX):
                    host.append((e.start, e.end, e.name))
    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)
                      and p.lines.get(OPS_LINE)), key=lambda p: p.name)
    if window is None or not devices:
        return None
    w0, w1 = window
    host = sorted((max(a, w0), min(b, w1), n) for a, b, n in host
                  if b > w0 and a < w1)
    ops, kernel_ns, kernel_n, idle, busy_total = {}, {}, {}, {}, 0
    longest = []
    for p in devices:
        spans = []
        for e in p.lines[OPS_LINE]:
            a, b = max(e.start, w0), min(e.end, w1)
            if b <= a:
                continue
            spans.append((a, b))
            key = short_name(e.name)
            ops[key] = ops.get(key, 0) + (b - a)
            for kname, pat in KERNELS.items():
                if pat.search(e.name):
                    kernel_ns[kname] = kernel_ns.get(kname, 0) + (b - a)
                    kernel_n[kname] = kernel_n.get(kname, 0) + 1
        busy = _union(spans)
        busy_total += sum(b - a for a, b in busy)
        total, each = _attribute(_gaps(busy, w0, w1), host)
        for name, ns in total.items():
            idle[name] = idle.get(name, 0) + ns
        longest = sorted(longest + each, reverse=True)[:5]
    n = len(devices)
    return {"window_s": (w1 - w0) / 1e9,
            "devices": n,
            "busy_s": busy_total / n / 1e9,
            "ops_s": {k: v / 1e9 for k, v in ops.items()},
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "kernel_events": kernel_n,
            "idle_s": {k: v / n / 1e9 for k, v in idle.items()},
            "longest_gaps_s": [(ns / 1e9, name) for ns, name in longest]}


def breakdown(red: dict) -> dict:
    """The ``breakdown`` of a traced run's result line: the ten device
    operations that took the most time (seconds summed over the chips);
    the five longest idle gaps of any chip, each named by the host
    annotation that covers most of it ("longest: bench.step"), and the
    idle time under each host annotation, averaged over the chips
    ("total: bench.push")."""
    ops = sorted(red["ops_s"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["idle_s"].items(), key=lambda kv: -kv[1])[:5]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"longest: {k}", v]
                          for v, k in red["longest_gaps_s"]]
            + [[f"total: {k}", v] for k, v in idle]}
