"""Discovery by name, the benchmark file's shape, the peaks table and the
roofline's byte count."""
import os
import sys

# the benchmark's own modules (``harness``, ``run``) and the program
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import json                                                 # noqa: E402
import re                                                   # noqa: E402
import shutil                                               # noqa: E402

import pytest                                               # noqa: E402

from harness import roofline, spec                          # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bm():
    return spec.load_benchmark()


def test_every_cell_resolves(bm):
    for w in bm["workloads"]:
        cfg = spec.load_config(w["config"])
        spec.load_traffic(w["traffic"])
        spec.reference(cfg["reference"])
        assert w["config"] in {c["name"] for c in bm["configs"]}
        assert spec.cell(bm, w["name"]) is w
    with pytest.raises(spec.CellError):
        spec.cell(bm, "no.such.cell")


def test_every_metric_has_a_reader(bm):
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_metrics_for_a_cell(bm):
    e2e = {m["name"] for m in spec.metrics_for(bm, "gsm_tchfs.calls", False)}
    assert e2e == {"window_p50_ms", "setup_s"}
    layer = {m["name"] for m in spec.metrics_for(bm, "dvbs_r34.bulk8", True)}
    assert "push_us.bulk" in layer and "push_us.calls" not in layer


def test_benchmark_file_shape(bm):
    """The keys, names and limits a benchmark file must keep to."""
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and 1 <= bm["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    cells = {w["name"] for w in bm["workloads"]}
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(cells) // 2)
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:                      # setup_s, another, a per-layer
        got = [m for m in bm["end_to_end"]
               if w in m.get("workloads", cells)]
        assert len(got) >= 2
        assert any(w in m.get("workloads", cells) for m in bm["per_layer"])
    assert len(json.dumps(bm)) < 64 * 1024


def test_discovery_by_name_needs_no_edit(tmp_path):
    """A new configuration, traffic mix and metric are found as new
    files alone."""
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bench / "configs" / "gsm_tchfs.json").read_text())
    (bench / "configs" / "new_code.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(
        {"loop": "open", "links": 2, "push_frames": 1, "pool_pushes": 2,
         "chunk_frames": 1, "slots": 2}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    assert spec.load_config("new_code", bench) == cfg
    assert spec.load_traffic("new_mix", bench)["links"] == 2
    assert spec.reader("new_metric.calls", bench)(None) == 42.0
    with pytest.raises(spec.CellError):
        spec.reader("missing_metric", bench)


def test_peaks_refuse_an_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")


def test_unified_kernel_bytes():
    cfg = spec.load_config("dvbs_r34")          # L = 354, beta = 2, f = 288
    assert roofline.unified_kernel_bytes(1024, cfg) == \
        1024 * (4 * 354 * 2 + 4 * 288)
