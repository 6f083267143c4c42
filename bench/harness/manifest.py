"""Read ``BENCHMARK.json`` and resolve a cell to its files.

A cell names a configuration and a traffic mix.  The configuration is
``bench/configs/<config>.json``, the traffic mix ``bench/traffic/<mix>.json``
and each metric ``bench/metrics/<metric>.py``.  Nothing here knows any
particular cell: a new one is new files and a new manifest entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT_DIR = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with its configuration and traffic."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list        # manifest entries reported with --trace 0
    per_layer: list         # manifest entries reported with --trace 1
    bench_dir: str


def load_manifest(root: str = ROOT_DIR) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    listed = metric.get("workloads")
    return listed is None or workload in listed


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT_DIR,
            manifest: Optional[dict] = None) -> Cell:
    """The cell named ``workload``, with its configuration and traffic
    files read.  Raises KeyError for a name the manifest lacks."""
    man = manifest if manifest is not None else load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    cfg_entry = configs[w["config"]]
    bench_dir = os.path.join(root, man["paths"][0])
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in man["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in man["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir,
    )


def load_module(path: str, name: str):
    """Import the Python file at ``path`` (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: str, name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       "metric_" + name).read


def system(bench_dir: str, name: str):
    """The module ``bench/systems/<name>.py`` that builds a configuration's
    system under test and its reference."""
    return load_module(os.path.join(bench_dir, "systems", f"{name}.py"),
                       "system_" + name)


def counts(bench_dir: str, kernel: str):
    """The count module ``bench/counts/<kernel>.py`` (``flops(shapes)``
    and ``bytes_accessed(shapes)``)."""
    return load_module(os.path.join(bench_dir, "counts", f"{kernel}.py"),
                       "counts_" + kernel)


def peaks(bench_dir: str, device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown kind is an
    error, never a default."""
    table = _read_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
