"""Finds a cell's pieces by name: the configuration file, the traffic mix,
the generator, the family's reference, program adapter and work counts,
and one reader file per metric."""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]       # chipbench/
ROOT = BENCH.parent                               # the checkout


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "mixes" / f"{name}.json")


def generator(kind: str):
    return importlib.import_module(f"chipbench.generators.{kind}")


def family(cfg: dict):
    """(reference, program adapter, work counts) modules of the family."""
    fam = cfg["family"]
    return tuple(importlib.import_module(f"chipbench.{part}.{fam}")
                 for part in ("reference", "program", "work"))


def metric_reader(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, or, for a name
    split by cell group (``decode_step_ms.chat``), the reader of the
    quantity it splits (``metrics/decode_step_ms.py``)."""
    stem = name
    while not (BENCH / "metrics" / f"{stem}.py").exists() and "." in stem:
        stem = stem.rsplit(".", 1)[0]
    path = BENCH / "metrics" / f"{stem}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell_name: str, section: str) -> list[dict]:
    """The metrics of ``section`` ('end_to_end' or 'per_layer') that this
    cell reports: those that list it, or list no cells at all."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]
