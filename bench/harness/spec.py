"""Find everything by name: the cell in ``BENCHMARK.json``, its
configuration under ``bench/configs/``, its traffic mix under
``bench/traffic/``, its reference under ``bench/references/`` and the
reader of each metric under ``bench/metrics/``.

A later change adds a configuration, a traffic mix or a metric as new
files and entries; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

#: the checkout's root: this file is <root>/bench/harness/spec.py
ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class CellError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found
    or does not fit together."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"no {path}") from None


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[w['name'] for w in bm['workloads']]}")


def _json(kind: str, name: str, bench: Path) -> dict:
    path = Path(bench) / kind / f"{name}.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"no {kind} file {path}") from None


def load_config(name: str, bench: Path = BENCH) -> dict:
    return _json("configs", name, bench)


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    return _json("traffic", name, bench)


def metrics_for(bm: dict, cell_name: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace=False``) or per-layer
    metrics (``trace=True``): every entry that lists the cell under
    ``workloads``, or has no ``workloads`` key."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def _load_module(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench: Path = BENCH):
    """The ``read(run)`` function of a metric: ``bench/metrics/<name>.py``
    or, for a name split by suffix (``push_us.calls``), the shared reader
    of its stem (``bench/metrics/push_us.py``)."""
    d = Path(bench) / "metrics"
    for stem in (metric, metric.split(".")[0]):
        path = d / f"{stem}.py"
        if path.exists():
            return _load_module(path, f"bench_metric_{stem}").read
    raise CellError(f"no reader for metric {metric!r} under {d}")


def reference(name: str, bench: Path = BENCH):
    """The plain reference module a configuration names."""
    path = Path(bench) / "references" / f"{name}.py"
    if not path.exists():
        raise CellError(f"no reference {path}")
    return _load_module(path, f"bench_reference_{name}")
