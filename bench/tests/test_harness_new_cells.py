"""Rehearsals on the CPU of the cells ``umts_amr122.bulk8`` (the K=9
rate-1/3 code, 256 states) and ``dvbs_r34.bulk32.4chip`` (the frame mesh
over four devices), by their own entries in ``BENCHMARK.json``, at tiny
sizes with the Pallas kernel interpreted: each comes out correct, reports
its metrics, and fails the check when an answer is altered or when the
lower-precision control takes the program's place."""
import os
import sys

# the benchmark's own modules (``harness``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import time                                                 # noqa: E402

import jax                                                  # noqa: E402
import ml_dtypes                                            # noqa: E402
import pytest                                               # noqa: E402

from harness import runner, spec                            # noqa: E402

UMTS, MESH = "umts_amr122.bulk8", "dvbs_r34.bulk32.4chip"
SMALL = {
    UMTS: dict(links=2, push_frames=2, pool_pushes=2, chunk_frames=2,
               slots=2),
    MESH: dict(links=4, push_frames=2, pool_pushes=2, chunk_frames=2,
               slots=4),
}


@pytest.fixture()
def kernel_interpreted(monkeypatch):
    """What DecoderConfig resolves to on a TPU, in interpret mode."""
    import repro.core.pipeline as pipeline
    monkeypatch.setattr(pipeline, "platform_defaults", lambda: {
        "backend": "kernel", "interpret": True, "layout": "sublane"})


@pytest.fixture()
def four_cpu_devices():
    from jax.extend.backend import clear_backends
    old = jax.config.jax_num_cpu_devices
    clear_backends()
    jax.config.update("jax_num_cpu_devices", 4)
    try:
        yield jax.devices()
    finally:
        clear_backends()
        jax.config.update("jax_num_cpu_devices", old)


def _run(cell, devices=None, seconds=0.4, trace=False, over=None, **kw):
    return runner.run(cell, 2 ** 33 + 29, seconds, trace,
                      t_setup=time.perf_counter(),
                      devices=devices or jax.devices(),
                      traffic_over={**SMALL[cell], **(over or {})}, **kw)


def test_cells_are_in_the_benchmark():
    bm = spec.load_benchmark()
    assert spec.cell(bm, UMTS)["chips"] == 1
    assert spec.cell(bm, MESH)["chips"] == 4
    cfg = spec.load_config("umts_amr122")
    assert cfg["code"]["rate"] == "1/3" and "puncture" not in cfg["code"]
    for cell in (UMTS, MESH):
        e2e = {m["name"] for m in spec.metrics_for(bm, cell, False)}
        assert e2e == {"decoded_mbps", "setup_s"}
        layer = {m["name"] for m in spec.metrics_for(bm, cell, True)}
        assert layer == {"push_us.bulk", "dispatch_ms.bulk",
                         "plan_compiles.bulk", "kernel_ns_per_bit.bulk",
                         "viterbi_unified_roofline.bulk",
                         "device_idle_pct.bulk"}


def test_rehearse_umts_cell(kernel_interpreted):
    """The K=9 rate-1/3 bucket decodes a closed loop of terminated blocks
    exactly, with every launch shape warmed before the window."""
    out = _run(UMTS)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["bit_mismatches"]["value"] == 0
    assert out["checks"]["bits_compared"]["value"] > 0
    assert {"decoded_mbps", "setup_s"} <= set(out["metrics"])


def test_rehearse_umts_traced_run(kernel_interpreted):
    out = _run(UMTS, trace=True)
    assert out["correct"]
    got = out["metrics"]
    assert {"push_us.bulk", "dispatch_ms.bulk", "plan_compiles.bulk"} <= \
        set(got)
    assert got["plan_compiles.bulk"]["value"] == 0


def test_rehearse_four_chip_cell(kernel_interpreted, four_cpu_devices):
    out = _run(MESH, devices=four_cpu_devices)
    assert out["correct"] and out["device"]["count"] == 4
    assert out["checks"]["bit_mismatches"]["value"] == 0


def test_umts_altered_answer_is_not_correct(monkeypatch):
    from repro.serve.plan_cache import PlanCache
    orig = PlanCache.batch_decoder

    def batch_decoder(self, cfg, nframes, **kw):
        fn = orig(self, cfg, nframes, **kw)

        def altered(frames):
            out = fn(frames)
            return out.at[0, 0].set(1 - out[0, 0])
        return altered
    monkeypatch.setattr(PlanCache, "batch_decoder", batch_decoder)
    out = _run(UMTS)
    assert not out["correct"]
    assert out["checks"]["bit_mismatches"]["value"] > 0


def test_umts_lower_precision_control_is_not_correct():
    """At the configuration's Eb/N0 levels the reference computed in
    bfloat16, put in the program's place, fails the check that the
    program passes."""
    out = _run(UMTS, seconds=0.6, control_dtype=ml_dtypes.bfloat16,
               over=dict(links=8, push_frames=4, pool_pushes=4,
                         chunk_frames=4, slots=8))
    assert out["correct"] and out["checks"]["bit_mismatches"]["value"] == 0
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["bit_mismatches"]["value"] > 0
