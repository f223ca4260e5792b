"""The program's spans in a profiler trace: recorded on the CPU from a
small ``DecodeServer`` under the profiler sink, and built here with known
answers for the idle attribution by innermost span; the span tool
rehearsed on the CPU; the untraced run leaving the program's tracing
off."""
import os
import sys

# the benchmark's own modules (``harness``, ``trace_spans``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import time                                                 # noqa: E402

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402
import pytest                                               # noqa: E402

import trace_spans                                          # noqa: E402
from harness import runner, spans, trace as tr              # noqa: E402
from repro.obs import (NULL_TRACER, ProfilerTracer,          # noqa: E402
                       get_tracer, set_tracer)

PARTS = ("repro.push_admit", "repro.push_sanitize", "repro.push_stage",
         "repro.push_frame")
SMALL_CALLS = dict(links=6, pool_pushes=4, slots=3, warm_s=0.2)


@pytest.fixture(autouse=True)
def _tracer_off():
    yield
    set_tracer(None)


def _events(planes):
    return [e for p in planes for evs in p.lines.values() for e in evs]


def _inside(e, outer):
    return any(o.start <= e.start and e.end <= o.end for o in outer)


def test_recorded_server_spans_nest_in_the_loop_annotations(tmp_path):
    """A rate-3/4 and a rate-1/2 session pushed, stepped and polled under
    the benchmark's annotations, with the profiler sink installed."""
    from repro.core import DecoderConfig, FrameSpec
    from repro.serve import DecodeServer, PlanCache
    ann = jax.profiler.TraceAnnotation
    frame = FrameSpec(f=96, v1=18, v2=24, f0=24, v2s=24)
    rng = np.random.default_rng(0)
    set_tracer(ProfilerTracer())
    srv = DecodeServer(slots=2, cache=PlanCache())
    r34 = srv.open_session(DecoderConfig(spec=frame, rate="3/4"),
                           chunk_frames=2)
    r12 = srv.open_session(DecoderConfig(spec=frame), chunk_frames=2)
    pushes = {r34: lambda: rng.standard_normal(256).astype(np.float32),
              r12: lambda: rng.standard_normal((192, 2)).astype(np.float32)}
    tr.start(str(tmp_path))
    try:
        with ann(tr.WINDOW):
            for _ in range(3):
                with ann("bench.push"):
                    for sid, make in pushes.items():
                        srv.push(sid, make())
                with ann("bench.step"):
                    srv.step()
                with ann("bench.poll"):
                    for sid in pushes:
                        srv.poll(sid)
            with ann("bench.step"):
                srv.drain()
    finally:
        jax.profiler.stop_trace()
    planes = spans.load(tr.find_xplane(str(tmp_path)))
    evs = _events(planes)
    by = {}
    for e in evs:
        by.setdefault(e.name, []).append(e)
    for name in PARTS:
        assert len(by[name]) == 6
        assert all(_inside(e, by["repro.push"]) for e in by[name])
    assert all(_inside(e, by["repro.retire"])
               for e in by["repro.retire_wait"])
    assert all(_inside(e, by["repro.launch"]) for e in by["repro.h2d"])
    loop = [e for e in evs if e.name.startswith("bench.")
            and e.name != tr.WINDOW]
    program = [e for e in evs if e.name.startswith(spans.PREFIX)]
    assert program and all(_inside(e, loop) for e in program)

    got = spans.spans(planes)
    assert got["repro.push"]["count"] == 6
    for name in PARTS:
        assert got[name]["count"] == 6
    assert got["repro.retire_wait"]["count"] == \
        got["repro.retire"]["count"] > 0
    assert got["repro.h2d"]["count"] == got["repro.launch"]["count"] > 0
    push = got["repro.push"]
    assert push["self_s"] == pytest.approx(
        push["total_s"] - sum(got[n]["total_s"] for n in PARTS), abs=1e-9)
    # the benchmark's own reduction reads the same planes as its loader's
    assert tr.reduce(planes) is None and tr.reduce(
        tr.load(tr.find_xplane(str(tmp_path)))) is None   # no TPU plane


KERNEL = ('%unified_decode_frames.1 = s32[8,288,128]{2,1,0} '
          'custom-call(f32[8,708,128]{2,1,0} %x), '
          'custom_call_target="tpu_custom_call"')
LOOP = [("bench.window", 0, 1000), ("bench.push", 100, 400),
        ("bench.step", 400, 500), ("bench.poll", 500, 600),
        ("bench.wait", 600, 1000)]
PROGRAM = [("repro.retire", -50, 20),            # starts before the window
           ("repro.retire_wait", 5, 15),
           ("repro.push", 120, 380), ("repro.push_stage", 150, 240),
           ("repro.push_frame", 240, 370),
           ("repro.launch", 410, 490), ("repro.h2d", 420, 440)]
# idle gaps [130,260], [310,430], [435,720], [800,1000]: each straddles
# span edges
BUSY = [(0, 130), (260, 310), (430, 435), (720, 800)]


def _synthetic(host):
    E = tr.Event
    return [tr.Plane("/host:CPU", {"python": [E(*h) for h in host]}),
            tr.Plane("/device:TPU:0", {tr.OPS_LINE: [
                E(KERNEL, a, b) for a, b in BUSY]})]


def test_idle_by_innermost_span_and_the_existing_entries_unchanged():
    with_spans = _synthetic(LOOP + PROGRAM)
    red = spans.idle(with_spans)
    ns = {k: round(v * 1e9) for k, v in red["idle_s"].items()}
    assert ns == {"repro.push": 30, "repro.push_stage": 90,
                  "repro.push_frame": 80, "bench.push": 20,
                  "bench.step": 20, "repro.launch": 60, "repro.h2d": 15,
                  "bench.poll": 100, "bench.wait": 320}
    assert sum(ns.values()) == 1000 - sum(b - a for a, b in BUSY)
    entries = [[k, round(v * 1e9)] for k, v in spans.breakdown(red)]
    assert entries == [
        ["span: bench.wait", 320], ["span: bench.poll", 100],
        ["span: repro.push_stage", 90], ["span: repro.push_frame", 80],
        ["span: repro.launch", 60],
        ["span longest: bench.wait", 285], ["span longest: bench.wait", 200],
        ["span longest: repro.push_stage", 130]]
    # what the benchmark reports today comes out the same with the
    # program's spans in the trace
    assert tr.breakdown(tr.reduce(with_spans)) == \
        tr.breakdown(tr.reduce(_synthetic(LOOP)))
    assert spans.idle(_synthetic(PROGRAM)) is None      # no window


def test_spans_totals_self_time_and_window():
    got = spans.spans(_synthetic(LOOP + PROGRAM))
    ns = {k: (round(s["total_s"] * 1e9), round(s["self_s"] * 1e9),
              s["count"]) for k, s in got.items()}
    assert ns == {"repro.retire_wait": (10, 10, 1),
                  "repro.push": (260, 40, 1),
                  "repro.push_stage": (90, 90, 1),
                  "repro.push_frame": (130, 130, 1),
                  "repro.launch": (80, 60, 1), "repro.h2d": (20, 20, 1)}
    host_only = [p for p in _synthetic(LOOP + PROGRAM)
                 if not p.name.startswith(tr.DEVICE_PREFIX)]
    assert spans.spans(host_only) == got                # no device needed
    assert spans.spans(_synthetic(PROGRAM)) is None


def test_span_tool_rehearsed_on_the_cpu():
    """The tool's traced run with the sink, at a tiny size: the program's
    spans come back without a device plane, and the push's parts account
    for it; the sink's cost is each side's median against the other."""
    on = trace_spans.traced_run("gsm_tchfs.calls", 2 ** 33 + 5, 0.4, True,
                                jax.devices(), traffic_over=SMALL_CALLS)
    assert get_tracer() is NULL_TRACER
    assert on["correct"] and on["spans"]["repro.push"]["count"] > 0
    for name in PARTS:
        assert on["spans"][name]["count"] == on["spans"]["repro.push"][
            "count"]
    assert 0 < on["push_parts_pct"] <= 100
    assert "push_us.calls" in on["metrics"]
    rows = [{"sink": s, "metrics": {"push_us.calls": v, "setup_s": 1.0}}
            for s, v in ((False, 40.0), (True, 44.0), (True, 46.0),
                         (False, 38.0), (False, 42.0))]
    summ = trace_spans.summary(rows)
    assert summ["off"] == {"push_us.calls": 40.0}
    assert summ["on"] == {"push_us.calls": 45.0}
    assert summ["sink_cost_pct"] == {"push_us.calls": pytest.approx(12.5)}


def test_untraced_run_leaves_the_program_tracing_off(monkeypatch):
    """--trace 0: the server sees the disabled tracer on every push, and
    the profiler sink opens no span."""
    from repro.serve import DecodeServer
    seen, opened = set(), []
    push = DecodeServer.push

    def watched(self, sid, llr):
        seen.add(id(self.trace))
        seen.add(id(get_tracer()))
        return push(self, sid, llr)

    monkeypatch.setattr(DecodeServer, "push", watched)
    monkeypatch.setattr(ProfilerTracer, "span",
                        lambda self, name, **kw: opened.append(name))
    out = runner.run("gsm_tchfs.calls", 2 ** 33 + 7, 0.3, False,
                     t_setup=time.perf_counter(), devices=jax.devices(),
                     traffic_over=SMALL_CALLS)
    assert out["correct"] and out["attempted"] > 0
    assert seen == {id(NULL_TRACER)} and opened == []


def test_span_tool_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert trace_spans.main(["--workload", "dvbs_r34.bulk8",
                             "--seeds", "1"]) == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "TPU" in cap.err
