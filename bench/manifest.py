"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own:

    bench/configs/<config>.json     the configuration as it is run
    bench/traffic/<traffic>.json    the parameters of one traffic mix
    bench/metrics/<metric>.py       the reader of one per-layer metric
    bench/checks/<cell>.json        the correctness limits of one cell

so a cell is added by adding files and entries, never by editing a file.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class ManifestError(RuntimeError):
    pass


def load(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"{path} is missing")
    return json.loads(path.read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(manifest: dict, name: str) -> dict:
    return _by_name(manifest["workloads"], name, "workload")


def config(manifest: dict, name: str, root: Path = ROOT) -> dict:
    entry = _by_name(manifest["configs"], name, "config")
    path = Path(root) / entry["file"]
    if not path.is_file():
        raise ManifestError(f"config {name!r}: {path} is missing")
    return json.loads(path.read_text())


def traffic(name: str, bench: Path = BENCH) -> dict:
    path = Path(bench) / "traffic" / f"{name}.json"
    if not path.is_file():
        raise ManifestError(f"traffic mix {name!r}: {path} is missing")
    return json.loads(path.read_text())


def limits(cell_name: str, bench: Path = BENCH) -> dict:
    """The correctness limits of one cell, and the readings they were set
    from (``bench/checks/<cell>.json``)."""
    path = Path(bench) / "checks" / f"{cell_name}.json"
    if not path.is_file():
        raise ManifestError(f"cell {cell_name!r}: {path} is missing")
    return json.loads(path.read_text())


def _applies(metric: dict, cell_entry: dict, manifest: dict) -> bool:
    if "workloads" in metric:
        return cell_entry["name"] in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = _by_name(manifest["end_to_end"], moves, "end-to-end metric")
    return "workloads" not in e2e or cell_entry["name"] in e2e["workloads"]


def end_to_end(manifest: dict, cell_entry: dict) -> list[dict]:
    """The cell's end-to-end metrics (those without a ``workloads`` key
    apply to every cell)."""
    return [m for m in manifest["end_to_end"]
            if "workloads" not in m or cell_entry["name"] in m["workloads"]]


def per_layer(manifest: dict, cell_entry: dict) -> list[dict]:
    return [m for m in manifest["per_layer"]
            if _applies(m, cell_entry, manifest)]


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read(run) -> float | None`` function of one per-layer metric."""
    path = Path(bench) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"per-layer metric {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
