"""``BENCHMARK.json`` and the files it names, found by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own:

    bench/configs/<config>.json     the configuration as it is run
    bench/traffic/<traffic>.json    the parameters of one traffic mix
    bench/metrics/<metric>.py       the reader of one per-layer metric
    bench/checks/<cell>.json        the correctness limits of one cell

and everything that belongs to one architecture, by the published
``config.model_type`` of its configuration file, in two files:

    bench/reference/<model_type>.py the float32 reference and the counts
                                    of its work (``reference/common.py``)
    bench/mapping/<model_type>.py   its published keys onto the program's
                                    configuration (``bench/system.py``)

so a cell, a configuration or an architecture is added by adding files
and entries, never by editing a file.  An architecture's reference module
may also draw its own weight leaves (``draw``, the interface in
``reference/common.py``), as a state-space layer's decay and step leaves
must be drawn.

A metric's reader, ``read(run)``, gets the run's record
(``bench/run.py``): besides the requests, the tick plans and the reduced
trace, the program's own spans in the trace (``serving.step`` and its
stages, ``*.wait`` where the host blocked on the device: ``trace.spans``),
each request's ``queued_s`` (its wait for a slot), and ``engine_stats``,
every number of the engine's ``stats()`` at the window's open and close,
flattened to dotted keys (``kv_pool.leased_block_ticks``).  So a metric
of a new mechanism's spans or counters is one new reader file.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
import sys
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


def _module(path: Path, what: str):
    """The module in ``path``, loaded once per process."""
    if not path.is_file():
        raise ManifestError(f"{what}: {path} is missing")
    return _load(str(path))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    p = Path(path)
    name = "bench_" + re.sub(r"\W", "_", f"{p.parent.name}_{p.stem}")
    spec = importlib.util.spec_from_file_location(name, p)
    module = importlib.util.module_from_spec(spec)
    # a dataclass looks its module up while it is being made
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read(run) -> float | None`` function of one per-layer metric."""
    return _module(Path(bench) / "metrics" / f"{name}.py",
                   f"per-layer metric {name!r}").read


def model_type(cfg_file: dict) -> str:
    """The published architecture of a configuration file."""
    return cfg_file["config"]["model_type"]


def architecture(name: str, bench: Path = BENCH):
    """The reference module of the architecture whose ``model_type`` is
    ``name`` (``bench/reference/<name>.py``)."""
    return _module(Path(bench) / "reference" / f"{name}.py",
                   f"model_type {name!r} has no reference")


def mapping(name: str, bench: Path = BENCH):
    """The module that maps the published keys of the architecture whose
    ``model_type`` is ``name`` onto the program
    (``bench/mapping/<name>.py``)."""
    return _module(Path(bench) / "mapping" / f"{name}.py",
                   f"model_type {name!r} has no mapping")
