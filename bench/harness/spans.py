"""The program's own spans in a profiler trace: what the server did, on
the device's clock.

With the program's profiler sink installed (``repro.obs.ProfilerTracer``)
every synchronous span of the server is a host annotation named
``repro.<span>`` (``repro.push``, ``repro.push_stage``, ``repro.h2d``,
``repro.retire_wait``, ...), nested under the benchmark's ``bench.*``
annotations on the same thread. ``load`` keeps them beside what
``trace.load`` keeps, so ``trace.reduce`` reads its planes unchanged.

``spans`` sums each program span over the traced window (total, self
time, count); it needs no device plane. ``idle`` puts the device's idle
gaps against the innermost host annotation over them (a program span
where there is one, else the benchmark's annotation, else
``host:other``), which names the part of ``push`` or ``step`` that holds
the device idle; ``breakdown`` turns that into ``span:`` and ``span
longest:`` entries in the form of ``trace.breakdown``'s."""
from __future__ import annotations

from . import trace as tracing

PREFIX = "repro."
#: entries of each kind in ``breakdown``
TOP_SPANS = 5
TOP_GAPS = 3


def load(path: str) -> list[tracing.Plane]:
    """Planes of an ``.xplane.pb``: a device's operations line, and the
    host's ``bench.`` annotations and ``repro.`` program spans."""
    from jax.profiler import ProfileData
    planes = []
    for p in ProfileData.from_file(path).planes:
        device = p.name.startswith(tracing.DEVICE_PREFIX)
        lines = {}
        for ln in p.lines:
            keep = device and ln.name == tracing.OPS_LINE
            lines[ln.name] = [
                tracing.Event(e.name, int(e.start_ns), int(e.end_ns))
                for e in ln.events
                if keep or e.name.startswith((tracing.HOST_PREFIX, PREFIX))]
        planes.append(tracing.Plane(p.name, lines))
    return planes


def _window(planes):
    for p in planes:
        if not p.name.startswith(tracing.DEVICE_PREFIX):
            for evs in p.lines.values():
                for e in evs:
                    if e.name == tracing.WINDOW:
                        return e.start, e.end
    return None


def _host_lines(planes):
    for p in planes:
        if not p.name.startswith(tracing.DEVICE_PREFIX):
            yield from p.lines.values()


def spans(planes) -> dict | None:
    """{span name: {"total_s", "self_s", "count"}} of the program spans
    that start in the window; self time is the span's less its child
    program spans'. None when the trace has no window annotation."""
    window = _window(planes)
    if window is None:
        return None
    w0, w1 = window
    out = {}
    for evs in _host_lines(planes):
        stack = []              # open program spans: (end, stats or None)
        for e in sorted((e for e in evs if e.name.startswith(PREFIX)),
                        key=lambda e: (e.start, -e.end)):
            while stack and stack[-1][0] <= e.start:
                stack.pop()
            d = e.end - e.start
            if stack and stack[-1][1] is not None:
                stack[-1][1]["self_ns"] -= d          # a child's time
            s = None
            if w0 <= e.start < w1:
                s = out.setdefault(e.name, {"total_ns": 0, "self_ns": 0,
                                            "count": 0})
                s["total_ns"] += d
                s["self_ns"] += d
                s["count"] += 1
            stack.append((e.end, s))
    return {name: {"total_s": s["total_ns"] / 1e9,
                   "self_s": s["self_ns"] / 1e9, "count": s["count"]}
            for name, s in out.items()}


def _innermost(intervals):
    """Disjoint sorted (start, end, name) segments, each named by the
    innermost interval over it; intervals nest (one thread's)."""
    out, stack, t = [], [], None

    def close(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for a, b, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        close(a)
        if stack and a > t:
            out.append((t, a, stack[-1][1]))
        stack.append((min(b, stack[-1][0]) if stack else b, name))
        t = a
    close(float("inf"))
    return out


def idle(planes) -> dict | None:
    """The device's idle time in the window under each innermost host
    annotation, averaged over the chips (``idle_s``), and the longest
    gaps of any chip, each named by the innermost annotation over most of
    it (``longest_gaps_s``). None without a window or a device plane."""
    window = _window(planes)
    devices = [p for p in planes if p.name.startswith(tracing.DEVICE_PREFIX)
               and p.lines.get(tracing.OPS_LINE)]
    if window is None or not devices:
        return None
    w0, w1 = window
    host = [(max(e.start, w0), min(e.end, w1), e.name)
            for evs in _host_lines(planes) for e in evs
            if e.name != tracing.WINDOW and e.end > w0 and e.start < w1]
    segs = _innermost(host)
    idle_ns, longest = {}, []
    for p in devices:
        ops = []
        for e in p.lines[tracing.OPS_LINE]:
            a, b = max(e.start, w0), min(e.end, w1)
            if b > a:
                ops.append((a, b))
        busy = tracing._union(ops)
        total, each = tracing._attribute(tracing._gaps(busy, w0, w1), segs)
        for name, ns in total.items():
            idle_ns[name] = idle_ns.get(name, 0) + ns
        longest = sorted(longest + each, reverse=True)[:TOP_GAPS]
    n = len(devices)
    return {"idle_s": {k: v / n / 1e9 for k, v in idle_ns.items()},
            "longest_gaps_s": [(ns / 1e9, name) for ns, name in longest]}


def breakdown(red: dict) -> list:
    """``idle``'s result as breakdown entries: the idle time under the
    five innermost annotations that hold the most ("span:
    repro.push_stage"), then the three longest gaps ("span longest:
    repro.retire_wait")."""
    top = sorted(red["idle_s"].items(), key=lambda kv: -kv[1])[:TOP_SPANS]
    return ([[f"span: {k}", v] for k, v in top]
            + [[f"span longest: {k}", v] for v, k in red["longest_gaps_s"]])
