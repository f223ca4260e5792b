"""Observability layer (repro.obs): span nesting and attributes, the
pay-nothing disabled tracer, histogram percentile accuracy vs
np.percentile, Chrome trace-event schema validity, Prometheus
parseability, and the end-to-end instrumentation of the serve/stream
pipeline (nested launch spans, async chunk overlap, plan_decode
attributes, trace-time kernel events)."""
import json
import re
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.obs import (Histogram, NULL_TRACER, Tracer, chrome_trace,
                       geometric_bounds, get_tracer, prometheus_text,
                       set_tracer, write_chrome_trace)
from repro.obs.tracer import NullTracer


@pytest.fixture(autouse=True)
def _restore_global_tracer():
    """Every test leaves the process-global tracer disabled — a leaked
    enabled tracer would silently record the rest of the suite."""
    yield
    set_tracer(None)


# ---------------------------------------------------------------- tracer

def test_span_nesting_parent_and_attrs():
    t = Tracer()
    with t.span("outer", a=1):
        with t.span("inner") as sp:
            sp.set(b="two")
    recs = {r.name: r for r in t.spans()}
    assert recs["inner"].parent == "outer"
    assert recs["outer"].parent is None
    assert recs["inner"].attrs == {"b": "two"}
    assert recs["outer"].attrs == {"a": 1}
    assert recs["outer"].dur >= recs["inner"].dur >= 0.0
    # inner completed first, so it is recorded first
    assert [r.name for r in t.spans()] == ["inner", "outer"]


def test_span_records_error_attr_on_exception():
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (rec,) = t.spans()
    assert rec.attrs["error"] == "RuntimeError"


def test_async_spans_overlap_and_end_is_idempotent():
    t = Tracer()
    a = t.begin("chunk", i=0)
    b = t.begin("chunk", i=1)
    b.end(bits=64)
    a.end()
    a.end()                                     # second end: no-op
    recs = t.spans()
    assert len(recs) == 2
    assert all(r.kind == "async" for r in recs)
    assert recs[0].sid != recs[1].sid           # distinct pairing ids
    assert recs[0].attrs == {"i": 1, "bits": 64}


def test_events_and_counters():
    t = Tracer()
    with t.span("launch"):
        t.event("retry", attempt=1)
    t.count("hits")
    t.count("hits", 2)
    (ev, sp) = t.spans()
    assert (ev.kind, ev.dur, ev.parent) == ("instant", 0.0, "launch")
    assert t.counters() == {"hits": 3}
    t.clear()
    assert t.spans() == [] and t.counters() == {}


def test_ring_buffer_caps_retained_spans():
    t = Tracer(capacity=8)
    for i in range(20):
        with t.span("s", i=i):
            pass
    recs = t.spans()
    assert len(recs) == 8
    assert [r.attrs["i"] for r in recs] == list(range(12, 20))


def test_tracer_is_thread_safe():
    t = Tracer()

    def work(k):
        for i in range(200):
            with t.span("w", k=k):
                t.count("n")

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.counters()["n"] == 800
    assert len(t.spans()) == 800
    # nesting state is per-thread: every span is a root in its own thread
    assert all(r.parent is None for r in t.spans())


def test_null_tracer_pays_nothing():
    """The disabled path returns ONE shared no-op object — no allocation
    per call — and records nothing."""
    n = NullTracer()
    assert n.span("a") is n.span("b")
    assert n.begin("a") is n.span("b")
    with n.span("a") as sp:
        sp.set(x=1)
    n.begin("c").end()
    n.event("e")
    n.count("k")
    assert n.spans() == [] and n.counters() == {}
    assert not n.enabled


def test_global_registry_set_get_restore():
    assert get_tracer() is NULL_TRACER
    t = Tracer()
    prev = set_tracer(t)
    assert prev is NULL_TRACER
    assert get_tracer() is t
    assert set_tracer(None) is t
    assert get_tracer() is NULL_TRACER


# ------------------------------------------------------------- histogram

def test_histogram_percentiles_track_np_percentile():
    rng = np.random.default_rng(0)
    samples = np.exp(rng.normal(1.0, 1.2, size=5000))   # lognormal ms
    h = Histogram.latency_ms()
    h.extend(samples)
    for p in (50, 90, 99):
        exact = float(np.percentile(samples, p))
        got = h.percentile(p)
        # geometric buckets at ratio 2**0.25 => <=~19% bucket resolution
        assert abs(got - exact) / exact < 0.25, (p, got, exact)
    assert h.count == 5000
    assert abs(h.mean() - samples.mean()) / samples.mean() < 1e-6


def test_histogram_degenerate_distribution_is_exact():
    h = Histogram.latency_ms()
    h.extend([3.7] * 100)
    assert h.percentile(50) == pytest.approx(3.7)
    assert h.percentile(99) == pytest.approx(3.7)
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["max"] == pytest.approx(3.7)


def test_histogram_empty_merge_and_bounds_mismatch():
    h = Histogram.latency_ms()
    assert h.percentile(99) == 0.0 and h.mean() == 0.0
    other = Histogram.latency_ms()
    other.extend([1.0, 2.0])
    h.merge(other)
    assert h.count == 2 and h.vmax == 2.0
    with pytest.raises(ValueError):
        h.merge(Histogram.sizes())


def test_geometric_bounds_cover_range():
    b = geometric_bounds(1.0, 100.0, 2.0)
    assert b[0] == 1.0 and b[-1] >= 100.0
    assert all(y == 2 * x for x, y in zip(b, b[1:]))


# ------------------------------------------------------------- exporters

def test_chrome_trace_schema_and_async_pairing(tmp_path):
    t = Tracer()
    with t.span("launch", bucket="b0"):
        with t.span("batch_pack"):
            pass
        t.event("retry", attempt=1)
    h = t.begin("inflight", frames=8)
    h.end()
    t.count("kernel_traces", 3)
    path = tmp_path / "trace.json"
    write_chrome_trace(t, str(path))
    obj = json.loads(path.read_text())          # valid JSON on disk
    ev = obj["traceEvents"]
    xs = [e for e in ev if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"launch", "batch_pack"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
    pack = next(e for e in xs if e["name"] == "batch_pack")
    assert pack["args"]["parent"] == "launch"
    begins = [e for e in ev if e["ph"] == "b"]
    ends = [e for e in ev if e["ph"] == "e"]
    assert len(begins) == len(ends) == 1
    assert begins[0]["id"] == ends[0]["id"]
    (inst,) = [e for e in ev if e["ph"] == "i"]
    assert inst["name"] == "retry"
    assert obj["otherData"]["counters"] == {"kernel_traces": 3}


def test_chrome_trace_stringifies_exotic_attr_values():
    t = Tracer()
    with t.span("s", shape=(4, 2), arr=np.arange(2)):
        pass
    obj = chrome_trace(t)
    args = obj["traceEvents"][-1]["args"]
    assert args["shape"] == "(4, 2)"
    assert isinstance(args["arr"], str)
    json.dumps(obj)                             # everything serializable


_EXPO_LINE = re.compile(
    r'^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)'
    r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="[^"]*"'
    r'(,[a-zA-Z0-9_]+="[^"]*")*\})? -?[0-9.e+-]+)$')


def test_prometheus_text_parses_line_by_line():
    snap = {"totals": {"launches": 4, "mbps": 1.25, "health": "ok"},
            "sessions": 2,
            "buckets": [{"bucket": "K7-f64", "launches": 4,
                         "p50_ms": 0.5, "last_error": "boom \"q\""}],
            "stages": {"launch_ms": {"count": 4, "p50": 0.4, "p99": 0.9,
                                     "max": 1.0, "mean": 0.5, "total": 2.0}},
            "plan_cache": {"entries": 2, "hits": 5, "misses": 2,
                           "traces": 2, "build_ms": 1.5}}
    text = prometheus_text(snap)
    lines = text.strip().split("\n")
    assert lines, "empty exposition"
    for line in lines:
        assert _EXPO_LINE.match(line), f"unparseable line: {line!r}"
    assert "# TYPE repro_serve_launches counter" in lines
    assert "repro_serve_mbps 1.25" in lines
    assert any(l.startswith('repro_serve_bucket_launches{bucket="K7-f64"}')
               for l in lines)
    assert any('stage="launch_ms"' in l and 'stat="p99"' in l
               for l in lines)
    # non-numeric fields (health, last_error) never reach the exposition
    assert "health" not in text and "boom" not in text


def test_prometheus_text_emits_true_histograms():
    """`stages_hist` must come out as real Prometheus histogram series:
    cumulative `_bucket{le=...}` samples ending at le="+Inf" whose count
    equals `_count`, plus `_sum` — the shape histogram_quantile() needs."""
    snap = {"totals": {},
            "stages_hist": {
                "queue_wait_ms": {"buckets": [[0.5, 2], [2.0, 5],
                                              ["+Inf", 7]],
                                  "sum": 6.25, "count": 7},
                "launch_ms": {"buckets": [[1.0, 1], ["+Inf", 1]],
                              "sum": 0.8, "count": 1}}}
    text = prometheus_text(snap)
    lines = text.strip().split("\n")
    for line in lines:
        assert _EXPO_LINE.match(line), f"unparseable line: {line!r}"
    # one TYPE header for the whole family, even with two stages
    assert lines.count("# TYPE repro_serve_stage_ms histogram") == 1
    q = [l for l in lines if 'stage="queue_wait_ms"' in l]
    assert 'repro_serve_stage_ms_bucket{le="0.5",stage="queue_wait_ms"} 2' \
        in q
    assert 'repro_serve_stage_ms_bucket{le="2.0",stage="queue_wait_ms"} 5' \
        in q
    assert 'repro_serve_stage_ms_bucket{le="+Inf",stage="queue_wait_ms"} 7' \
        in q
    assert 'repro_serve_stage_ms_sum{stage="queue_wait_ms"} 6.25' in q
    assert 'repro_serve_stage_ms_count{stage="queue_wait_ms"} 7' in q
    # counts are cumulative (monotone non-decreasing up to +Inf == _count)
    counts = [int(l.rsplit(" ", 1)[1]) for l in q if "_bucket{" in l]
    assert counts == sorted(counts) and counts[-1] == 7


def test_server_snapshot_histograms_round_trip_exposition():
    """End to end: a served workload's metrics_snapshot() carries
    stages_hist, and its exposition parses with cumulative buckets."""
    srv, sids = _serve_workload(None)
    snap = srv.metrics_snapshot()
    hists = snap["stages_hist"]
    for stage in ("queue_wait_ms", "launch_ms", "retire_ms"):
        h = hists[stage]
        assert h["count"] > 0
        assert h["buckets"][-1][0] == "+Inf"
        assert h["buckets"][-1][1] == h["count"]
    json.dumps(snap)                    # "+Inf" as string: strict JSON
    text = prometheus_text(snap)
    for line in text.strip().split("\n"):
        assert _EXPO_LINE.match(line), f"unparseable line: {line!r}"
    assert "# TYPE repro_serve_stage_ms histogram" in text


# -------------------------------------------------- pipeline integration

def _serve_workload(trace, faults=None, **srv_kw):
    from repro.core import DecoderConfig, FrameSpec
    from repro.serve import DecodeServer, PlanCache
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    cfg = DecoderConfig(spec=spec)
    rng = np.random.default_rng(0)
    n = 2 * 5 * spec.f
    rx = rng.standard_normal((n, 2)).astype(np.float32)
    srv = DecodeServer(slots=2, cache=PlanCache(), trace=trace,
                       faults=faults, **srv_kw)
    sids = [srv.open_session(cfg, chunk_frames=5) for _ in range(2)]
    for r in range(2):
        for sid in sids:
            srv.push(sid, rx[r * (n // 2):(r + 1) * (n // 2)])
        while srv.step():
            pass
    return srv, sids


def test_server_spans_nest_and_stage_breakdown_lands_in_snapshot():
    t = Tracer()
    srv, sids = _serve_workload(t)
    for sid in sids:
        srv.close_session(sid)
    names = {r.name for r in t.spans()}
    assert {"push", "launch", "batch_pack", "launch_attempt",
            "retire", "inflight"} <= names
    by_name = {}
    for r in t.spans():
        by_name.setdefault(r.name, []).append(r)
    assert all(r.parent == "launch" for r in by_name["batch_pack"])
    assert all(r.parent == "launch" for r in by_name["launch_attempt"])
    assert all(r.kind == "async" for r in by_name["inflight"])
    snap = srv.metrics_snapshot()
    stages = snap["stages"]
    for stage in ("queue_wait_ms", "batch_pack_ms", "launch_ms",
                  "retire_ms"):
        assert stages[stage]["count"] > 0, stage
    tot = snap["totals"]
    assert tot["mbps"] > 0 and tot["uptime_s"] > 0
    assert all(row["uptime_s"] > 0 for row in snap["buckets"])


#: the sync spans inside push, _dispatch and _retire
PUSH_PARTS = ("push_sanitize", "push_admit", "push_stage", "push_frame")


def test_server_sub_spans_nest_under_push_launch_and_retire():
    t = Tracer()
    srv, sids = _serve_workload(t)
    for sid in sids:
        srv.close_session(sid)
    recs = [r for r in t.spans() if r.kind == "span"]
    parent = {}
    for r in recs:
        parent.setdefault(r.name, set()).add(r.parent)
    for name in PUSH_PARTS:
        assert parent[name] == {"push"}, name
    assert parent["h2d"] == {"launch"}
    assert parent["retire_wait"] == {"retire"}
    n = {name: sum(r.name == name for r in recs)
         for name in PUSH_PARTS + ("push", "launch", "h2d", "retire",
                                   "retire_wait")}
    assert len({n[k] for k in ("push",) + PUSH_PARTS}) == 1
    assert n["h2d"] == n["launch"] and n["retire_wait"] == n["retire"]


def test_null_tracer_serve_call_sites_share_one_no_op():
    """With the disabled tracer every span the server opens is the one
    shared no-op object, and ``push`` opens none of its sub-spans."""
    from repro.obs.tracer import _NULL_SPAN

    class Spy(NullTracer):
        def __init__(self):
            self.got = []

        def span(self, name, **attrs):
            out = super().span(name, **attrs)
            self.got.append((name, out))
            return out

    spy = Spy()
    srv, sids = _serve_workload(spy)
    for sid in sids:
        srv.close_session(sid)
    names = {name for name, _ in spy.got}
    assert {"push", "h2d", "retire_wait"} <= names
    assert not names & set(PUSH_PARTS)
    assert all(out is _NULL_SPAN for _, out in spy.got)
    srv, _ = _serve_workload(None)               # the default
    assert srv.trace is NULL_TRACER


@pytest.mark.parametrize("kind", ["null", "ring", "profiler"])
def test_push_paths_traced_and_untraced_agree(kind):
    """``push`` under each kind of tracer against the untraced default:
    the same bits back, and the same backpressure refusal before anything
    is absorbed."""
    from repro.core import DecoderConfig, FrameSpec
    from repro.obs import ProfilerTracer
    from repro.serve import Backpressure
    trace = {"null": NULL_TRACER, "ring": Tracer(),
             "profiler": ProfilerTracer()}[kind]
    srv, sids = _serve_workload(trace)
    ref, ref_sids = _serve_workload(None)
    for a, b in zip(sids, ref_sids):
        got = np.concatenate([srv.poll(a), srv.close_session(a)])
        want = np.concatenate([ref.poll(b), ref.close_session(b)])
        assert got.size and np.array_equal(got, want)
    cfg = DecoderConfig(spec=FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20))
    sid = srv.open_session(cfg, chunk_frames=5)
    big = np.zeros((64 * 5 * (srv.queue_depth + 2), 2), np.float32)
    with pytest.raises(Backpressure, match="queue_depth"):
        srv.push(sid, big)
    assert srv.session_state(sid)["inflight"] == 0
    srv.push(sid, big[:64 * 5 * 2])              # nothing was absorbed
    assert srv.session_state(sid)["inflight"] == 1


def test_profiler_sink_names_spans_for_the_profiler(tmp_path):
    """Installed through set_tracer, the sink turns each sync span into a
    ``repro.<name>`` profiler annotation, nested as the spans are; the
    ring, counters and async spans stay empty."""
    from repro.obs import ProfilerTracer
    sink = ProfilerTracer()
    set_tracer(sink)
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv, sids = _serve_workload(None)
        assert srv.trace is sink
        for sid in sids:
            srv.close_session(sid)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    evs = [e for p in jax.profiler.ProfileData.from_file(str(path)).planes
           for ln in p.lines for e in ln.events
           if e.name.startswith("repro.")]
    names = {e.name for e in evs}
    assert {"repro." + n for n in ("push", "launch", "batch_pack", "h2d",
                                   "launch_attempt", "retire",
                                   "retire_wait") + PUSH_PARTS} <= names
    pushes = [e for e in evs if e.name == "repro.push"]
    for part in [e for e in evs if e.name in
                 {"repro." + n for n in PUSH_PARTS}]:
        assert any(p.start_ns <= part.start_ns
                   and part.start_ns + part.duration_ns
                   <= p.start_ns + p.duration_ns for p in pushes)
    assert sink.spans() == [] and sink.counters() == {}
    with sink.span("x") as sp:
        assert sp.set(a=1) is sp
    assert sink.begin("y") is sink.begin("z")    # async: the shared no-op


def test_server_retry_and_degrade_spans_under_faults():
    from repro.testing import FaultInjector, FaultSpec
    t = Tracer()
    faults = FaultInjector(FaultSpec("launch_error", every=1), seed=0)
    srv, sids = _serve_workload(t, faults=faults, max_retries=1,
                                backoff_s=0.0)
    for sid in sids:
        srv.close_session(sid)
    names = {r.name for r in t.spans()}
    assert "retry" in names or "degrade" in names
    attempts = [r for r in t.spans() if r.name == "launch_attempt"]
    assert any(r.attrs.get("attempt", 0) > 0 or "error" in r.attrs
               for r in attempts)


def test_stream_decoder_emits_async_chunk_spans():
    from repro.core import DecoderConfig, FrameSpec
    from repro.core.stream import make_stream_decoder
    t = Tracer()
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    dec = make_stream_decoder(DecoderConfig(spec=spec), chunk_frames=4,
                              trace=t)
    rng = np.random.default_rng(0)
    n = 3 * 4 * spec.f
    out = np.concatenate([
        dec.push(rng.standard_normal((n, 2)).astype(np.float32)),
        dec.flush()])
    assert out.size == n
    chunks = [r for r in t.spans() if r.name == "chunk"]
    assert len(chunks) == 3 and all(r.kind == "async" for r in chunks)
    assert {r.name for r in t.spans()} >= {"push", "flush", "dispatch"}


def test_plan_decode_span_carries_chosen_plan_and_vmem():
    from repro.core import FrameSpec, STD_K7
    from repro.kernels.autotune import plan_decode
    t = Tracer()
    set_tracer(t)
    spec = FrameSpec(f=256, v1=20, v2=45, f0=32, v2s=45)
    plan = plan_decode(STD_K7, spec)
    (rec,) = [r for r in t.spans() if r.name == "plan_decode"]
    a = rec.attrs
    assert a["frames_per_tile"] == plan.frames_per_tile
    assert a["chunk_frames"] == plan.chunk_frames
    assert a["vmem_bytes"] > 0 and a["vmem_budget"] > 0
    assert a["fits"] is True
    assert a["fingerprint"] == plan.fingerprint()


def test_kernel_trace_event_fires_once_per_compile():
    from repro.core import FrameSpec, STD_K7
    from repro.core.framed import frame_llr
    from repro.kernels import ops
    t = Tracer()
    set_tracer(t)
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    rng = np.random.default_rng(0)
    llr = jnp.asarray(rng.standard_normal((8 * spec.f, 2)).astype(np.float32))
    frames = frame_llr(llr, spec)
    for _ in range(3):                       # re-launches hit the jit cache
        ops.viterbi_decode_frames(frames, STD_K7, spec,
                                  frames_per_tile=8).block_until_ready()
    evs = [r for r in t.spans() if r.name == "kernel_trace"]
    assert len(evs) == 1                     # one real compile
    assert evs[0].attrs["frames_per_tile"] == 8
    assert evs[0].attrs["frames_per_tile"] == 8
    assert t.counters()["kernel_traces"] == 1


def test_plan_cache_counts_hits_misses_and_build_time():
    from repro.core import DecoderConfig, FrameSpec
    from repro.serve.plan_cache import PlanCache
    t = Tracer()
    set_tracer(t)
    cache = PlanCache()
    cfg = DecoderConfig(spec=FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20))
    cache.frame_decoder(cfg)
    cache.frame_decoder(cfg)
    st = cache.stats()
    assert st["misses"] == 1 and st["hits"] == 1
    assert [r.name for r in t.spans()] == ["plan_build"]
    assert t.counters() == {}                # the stats are the counters
    assert st["build_ms"] >= 0.0


def test_record_fault_rejects_unknown_counter():
    from repro.serve.metrics import BucketMetrics
    m = BucketMetrics("b0")
    with pytest.raises(ValueError, match="unknown fault counter"):
        m.record_fault("not_a_counter")
    m.record_fault("retries", error="e1", n=2)
    assert m.retries == 2 and m.last_error == "e1"


def _kernel_server_k5():
    from repro.core import DecoderConfig, FrameSpec, make_trellis
    from repro.serve import DecodeServer, PlanCache
    spec = FrameSpec(f=64, v1=16, v2=20, f0=16, v2s=20)
    cfg = DecoderConfig(trellis=make_trellis(5, (0o23, 0o35)), spec=spec,
                        backend="kernel", interpret=True)
    srv = DecodeServer(slots=2, cache=PlanCache())
    sid = srv.open_session(cfg, chunk_frames=2)
    rx = np.random.default_rng(0).standard_normal((5 * 64, 2))
    srv.push(sid, rx.astype(np.float32))
    srv.drain()
    return srv


def test_plan_build_and_snapshot_name_what_the_plan_chose():
    """Under a recording tracer the ``plan_build`` span of a kernel plan
    carries its states, beta, frame tile and planned VMEM bytes; the
    server's snapshot lists the same per bucket, and ``kernel_trace``
    names the states."""
    t = Tracer()
    set_tracer(t)
    srv = _kernel_server_k5()
    builds = [r.attrs for r in t.spans() if r.name == "plan_build"]
    batch = [a for a in builds if a["kind"] == "batch"]
    assert batch and all(a["states"] == 16 and a["beta"] == 2
                         for a in builds)
    assert all(a["frames_per_tile"] >= 4 and a["vmem_bytes"] > 0
               for a in batch)
    (plan,) = srv.metrics_snapshot()["plans"].values()
    assert plan == {k: batch[0][k] for k in
                    ("states", "beta", "frames_per_tile", "vmem_bytes")}
    traces = [r.attrs for r in t.spans() if r.name == "kernel_trace"]
    assert traces and all(a["states"] == 16 for a in traces)


def test_plan_build_attributes_cost_nothing_untraced(monkeypatch):
    """With no tracer recording, a plan build never computes them."""
    import repro.serve.plan_cache as plan_cache

    def refuse(*a, **k):
        raise AssertionError("plan attributes computed untraced")
    monkeypatch.setattr(plan_cache, "plan_attrs", refuse)
    _kernel_server_k5()
