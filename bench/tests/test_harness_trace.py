"""The reduction from a profiler trace to the per-layer metrics, on a
trace built here with known answers, and on one recorded on the CPU."""
import os
import sys

# the benchmark's own modules (``harness``, ``run``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest                                               # noqa: E402

from harness import trace as tr                             # noqa: E402

KERNEL = ('%unified_decode_frames.1 = s32[8,288,128]{2,1,0:T(8,128)S(1)} '
          'custom-call(f32[8,708,128]{2,1,0:T(8,128)S(1)} %copy_bitcast_'
          'fusion), custom_call_target="tpu_custom_call"')
RESHAPE = ('%reshape.4 = f32[8,128,708]{1,0,2:T(8,128)S(1)} '
           'reshape(f32[1024,354,2]{0,2,1:T(2,128)} %frames.1)')
COPY = ('%copy.3 = s32[64,189]{1,0:T(8,128)} '
        'copy(s32[64,189]{0,1:T(8,128)S(1)} %bitcast.7)')


def _planes(device_ops, host):
    E = tr.Event
    return [
        tr.Plane("/host:CPU", {"python": [E(n, a, b) for n, a, b in host]}),
        tr.Plane("/device:TPU:0",
                 {tr.OPS_LINE: [E(n, a, b) for n, a, b in device_ops],
                  "XLA Modules": []}),
    ]


def test_reduce_busy_kernel_and_idle_attribution():
    host = [("bench.window", 0, 1000), ("bench.push", 100, 400),
            ("bench.step", 400, 500), ("bench.poll", 500, 600),
            ("bench.wait", 600, 1000)]
    ops = [(RESHAPE, 50, 150), (KERNEL, 140, 300), (COPY, 700, 800),
           (COPY, 1200, 1300)]                       # after the window
    red = tr.reduce(_planes(ops, host))
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(350e-9)    # [50,300] + [700,800]
    assert red["kernel_s"] == {"viterbi_unified": pytest.approx(160e-9)}
    assert red["kernel_events"] == {"viterbi_unified": 1}
    # gaps [0,50], [300,700], [800,1000] against the host's annotations
    assert red["idle_s"] == {
        "host:other": pytest.approx(50e-9),
        "bench.push": pytest.approx(100e-9),
        "bench.step": pytest.approx(100e-9),
        "bench.poll": pytest.approx(100e-9),
        "bench.wait": pytest.approx(300e-9)}
    assert red["ops_s"]["copy.3 copy s32[64,189]"] == pytest.approx(100e-9)
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["unified_decode_frames.1 custom-call "
                                   "s32[8,288,128]", pytest.approx(160e-9)]
    assert bd["idle_gaps"][0] == ["longest: bench.push",
                                  pytest.approx(400e-9)]
    assert ["total: bench.wait", pytest.approx(300e-9)] in bd["idle_gaps"]
    assert [k for k, _ in bd["idle_gaps"]].index("longest: bench.wait") \
        == 1                                          # the [800,1000] gap
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_averages_over_devices():
    host = [("bench.window", 0, 100)]
    planes = _planes([(KERNEL, 0, 50)], host) + [tr.Plane(
        "/device:TPU:1", {tr.OPS_LINE: [tr.Event(KERNEL, 0, 100)]})]
    red = tr.reduce(planes)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(75e-9)     # mean of 50 and 100
    assert red["kernel_s"]["viterbi_unified"] == pytest.approx(150e-9)


def test_reduce_needs_window_and_device():
    assert tr.reduce(_planes([(KERNEL, 0, 5)], [])) is None
    assert tr.reduce([tr.Plane("/host:CPU", {"python": [
        tr.Event("bench.window", 0, 9)]})]) is None


def test_kernel_pattern_matches_the_kernel_only():
    pat = tr.KERNELS["viterbi_unified"]
    assert pat.search(KERNEL)
    consumer = ('%copy_bitcast_fusion.1 = s32[1024,288]{0,1:T(8,128)} '
                'fusion(s32[8,288,128]{2,1,0} %unified_decode_frames.1)')
    assert not pat.search(consumer) and not pat.search(RESHAPE)
    assert tr.short_name("not an hlo op") == "not an hlo op"


def test_recorded_cpu_trace_keeps_the_annotations(tmp_path):
    """A trace recorded here: the window and loop annotations come back
    from the ``.xplane.pb``; with no TPU plane there is nothing to
    reduce."""
    import jax
    import jax.numpy as jnp
    tr.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            with jax.profiler.TraceAnnotation("bench.step"):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    planes = tr.load(tr.find_xplane(str(tmp_path)))
    names = {e.name for p in planes for evs in p.lines.values()
             for e in evs}
    assert {tr.WINDOW, "bench.step"} <= names
    assert tr.reduce(planes) is None
