"""What a cell is, found by name: BENCHMARK.json, configuration files,
traffic files, the module that runs a mix's kind, a configuration's
model family, per-layer metric readers and the table of peaks.

Nothing here imports JAX or the program (only the modules ``runner``
and ``family`` look up may), so the tests and the harness's argument
handling load it without touching a device.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    return load_json(path)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def config(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "configs" / f"{name}.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "traffic" / f"{name}.json")


def limits(workload: str, root: Path = ROOT) -> dict:
    """{number compared: its limit} of one cell (``cells/<name>.json``)."""
    return load_json(root / "cells" / f"{workload}.json")["limits"]


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip of this kind; a kind not in the table
    is an error, never a default."""
    table = load_json(root / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


def by_name(*parts: str, what: str):
    """The module ``chipbench/<parts>.py``; where that file is missing, an
    error that names it."""
    path = ("chipbench", *parts)
    if not (all(p.isidentifier() for p in parts)
            and importlib.util.find_spec(".".join(path))):
        raise ModuleNotFoundError(f"no {what}: looked for {'/'.join(path)}.py")
    return importlib.import_module(".".join(path))


def runner(kind: str):
    """The module that runs a traffic mix of this ``kind``:
    ``<kind>_cell.py``, with ``setup``, ``window``, ``check``, ``build``."""
    return by_name(f"{kind}_cell", what=f"runner for the kind {kind!r}")


def family(conf: dict):
    """The model family a configuration file names: ``families/<family>.py``."""
    return by_name("families", conf["family"],
                   what=f"model family {conf['family']!r}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """Metric entries this cell reports: its end-to-end metrics, or with
    ``trace`` its per-layer metrics."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]
