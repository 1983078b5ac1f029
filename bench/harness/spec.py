"""Finds every benchmark item by its name.

``BENCHMARK.json`` names the cells; each cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each per-layer metric has a reader of
its own (``bench/metrics/<metric>.py``, a module with
``read(obs) -> float | None``).  Adding a configuration, a mix or a
metric is adding a file.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A name that does not resolve to a file, or a malformed file."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no such file: {os.path.relpath(path, ROOT)}") from None


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def find_workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"unknown workload {name!r}; known: "
                    f"{[c['name'] for c in bench['workloads']]}")


def load_config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _load_json(os.path.join(bench_dir, "configs", f"{name}.json"))


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_peaks(bench_dir: str = BENCH_DIR) -> dict:
    return _load_json(os.path.join(bench_dir, "peaks.json"))


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader for metric {name!r}: "
                        f"{os.path.relpath(path, ROOT)}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell_metrics(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: the end-to-end ones with
    ``--trace 0``, the per-layer ones with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if applies(m, cell_name)]
