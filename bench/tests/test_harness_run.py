"""Rehearsals of the benchmark's runs on the CPU: every cell at a tiny
size with the Pallas kernel interpreted (the platform's defaults are
steered here, in the test), the check failing under each fault a cell
can have and under the lower-precision control, and ``run.py``
refusing to run without a TPU."""
import os
import sys

# the benchmark's own modules (``harness``, ``run``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import json                                                 # noqa: E402
import time                                                 # noqa: E402

import jax                                                  # noqa: E402
import ml_dtypes                                            # noqa: E402
import pytest                                               # noqa: E402

import run as bench_run                                     # noqa: E402
from harness import runner, spec, system                    # noqa: E402

SMALL = {
    "dvbs_r34.bulk8": dict(links=2, push_frames=2, pool_pushes=2,
                           chunk_frames=2, slots=2),
    "gsm_tchfs.calls": dict(links=6, pool_pushes=4, slots=3, warm_s=0.2),
    "mesh4": dict(links=4, push_frames=2, pool_pushes=2, chunk_frames=2,
                  slots=4),
}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


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


@pytest.fixture()
def mesh_root(tmp_path):
    """A benchmark file whose one cell is the four-chip traffic of
    ``bench/traffic/bulk32.4chip.json`` on the DVB-S configuration."""
    bm = spec.load_benchmark()
    bm["workloads"] = [{"name": "mesh4", "config": "dvbs_r34",
                        "traffic": "bulk32.4chip", "chips": 4,
                        "why": "rehearsal"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp_path


def _run(cell, devices=None, seconds=0.4, trace=False, over=None, **kw):
    return runner.run(cell, 2 ** 33 + 17, seconds, trace,
                      t_setup=time.perf_counter(),
                      devices=devices or jax.devices(),
                      traffic_over={**SMALL[cell], **(over or {})}, **kw)


def _check_schema(out, trace):
    assert list(out)[:5] == RESULT_KEYS and list(out)[-1] == "checks"
    json.dumps(out)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])
    for c in out["checks"].values():
        assert "value" in c and ("max" in c or "min" in c)


@pytest.mark.parametrize("cell", ["dvbs_r34.bulk8", "gsm_tchfs.calls"])
def test_rehearse_cell(kernel_interpreted, cell):
    out = _run(cell)
    _check_schema(out, False)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    names = set(out["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert out["checks"]["bit_mismatches"]["value"] == 0


def test_rehearse_traced_run_reports_per_layer_metrics(kernel_interpreted):
    out = _run("gsm_tchfs.calls", trace=True)
    _check_schema(out, True)
    assert out["correct"]
    got = set(out["metrics"])
    assert {"push_us.calls", "plan_compiles.calls",
            "occupancy_pct.calls"} <= got
    assert out["metrics"]["plan_compiles.calls"]["value"] == 0
    assert "setup_s" not in got


def test_rehearse_four_chip_mesh(kernel_interpreted, four_cpu_devices,
                                 mesh_root):
    out = _run("mesh4", devices=four_cpu_devices, root=mesh_root)
    assert out["correct"] and out["device"]["count"] == 4


def _flip_first_bit(monkeypatch):
    from repro.serve.plan_cache import PlanCache
    orig = PlanCache.batch_decoder

    def batch_decoder(self, cfg, nframes, **kw):
        fn = orig(self, cfg, nframes, **kw)

        def altered(frames):
            out = fn(frames)
            return out.at[0, 0].set(1 - out[0, 0])
        return altered
    monkeypatch.setattr(PlanCache, "batch_decoder", batch_decoder)


@pytest.mark.parametrize("cell", ["dvbs_r34.bulk8", "gsm_tchfs.calls"])
def test_altered_answer_is_not_correct(monkeypatch, cell):
    _flip_first_bit(monkeypatch)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["bit_mismatches"]["value"] > 0


def test_missing_exchange_between_chips_is_not_correct(
        monkeypatch, four_cpu_devices, mesh_root):
    """Only the first chip's shard of each launch comes back."""
    import repro.distributed.stream as dstream
    orig = dstream.shard_map

    def shard_map(f, **kw):
        def first_shard_only(x):
            out = f(x)
            return jax.numpy.where(jax.lax.axis_index("frames") == 0,
                                   out, 0)
        return orig(first_shard_only, **kw)
    monkeypatch.setattr(dstream, "shard_map", shard_map)
    out = _run("mesh4", devices=four_cpu_devices, root=mesh_root)
    assert not out["correct"]
    assert out["checks"]["bit_mismatches"]["value"] > 0


def test_lower_precision_control_is_not_correct():
    """The reference computed in bfloat16, put in the program's place and
    checked as served bits are, comes out not correct where the program
    comes out correct."""
    out = _run("gsm_tchfs.calls", seconds=0.6,
               over=dict(links=24, pool_pushes=8, slots=8),
               control_dtype=ml_dtypes.bfloat16)
    assert out["correct"] and out["checks"]["bit_mismatches"]["value"] == 0
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["bit_mismatches"]["value"] > 0
    assert ctl["checks"]["bits_compared"] == out["checks"]["bits_compared"]


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert bench_run.main(["--workload", "dvbs_r34.bulk8", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "no TPU" in cap.err


def test_main_prints_the_checks_then_the_result(monkeypatch, capsys):
    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    fake = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
            "device": {}, "info": {"setup_s_at": {}},
            "checks": {"bit_mismatches":
                                     {"value": 0, "max": 0}}}
    monkeypatch.setattr(runner, "run", lambda *a, **k: fake)
    monkeypatch.setattr(system, "use_compile_cache", lambda: None)
    assert bench_run.main(["--workload", "dvbs_r34.bulk8", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 0
    cap = capsys.readouterr()
    assert json.loads(cap.out.strip().splitlines()[-1]) == fake
    assert cap.err.strip().splitlines()[-1] == \
        "check bit_mismatches 0 max 0"
