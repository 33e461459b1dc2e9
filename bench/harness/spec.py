"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration file (its ``file``
under ``configs``), a traffic mix (``bench/traffic/<traffic>.json``) and
the cell's own serving shapes and check limits
(``bench/cells/<cell>.json``).  Each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files
and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file: {path}") from None


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file as it is run
    traffic: dict          # the traffic mix's parameters
    shape: dict            # slots, cache, block and check limits of the cell
    end_to_end: List[dict]
    per_layer: List[dict]


def _reported_here(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:            # per-layer metric without a cell list
        return metric["moves"] in e2e_names
    return True


def cell(name: str, bench: Optional[dict] = None,
         bench_dir: str = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(by_name)})")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    root = os.path.dirname(bench_dir)
    e2e = [m for m in bench["end_to_end"] if _reported_here(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reported_here(m, name, names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=dict(load_json(os.path.join(root, conf["file"])),
                    name=conf["name"]),
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        shape=load_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def _module(path: str, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    if spec is None or not os.path.exists(path):
        raise SpecError(f"missing benchmark module: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    return _module(path, "bench_metric_" + name.replace(".", "_")).read


def family(model_type: str, bench_dir: str = BENCH_DIR):
    """(program adapter, plain reference) modules of a model family."""
    return (_module(os.path.join(bench_dir, "models", model_type + ".py"),
                    "bench_model_" + model_type),
            _module(os.path.join(bench_dir, "reference", model_type + ".py"),
                    "bench_reference_" + model_type))


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
