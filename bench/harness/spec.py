"""``BENCHMARK.json`` and the files it names, found by name.

A cell is ``<config>.<traffic>``.  Its configuration is the file that
``BENCHMARK.json`` gives for the config, its traffic mix is
``bench/mixes/<traffic>.json``, its app adapter is
``bench/apps/<app>.py`` (``app`` is a key of the configuration), and each
metric is read by ``bench/metrics/<metric>.py``.  Adding a cell, a mix, an
app or a metric is adding files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: dict
    mix: dict
    app: str
    end_to_end: list     # entries of BENCHMARK.json's end_to_end for this cell
    per_layer: list      # entries of per_layer for this cell


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = REPO_ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = REPO_ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    bench = benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = load_json(os.path.join(root, "bench", "mixes",
                                 w["traffic"] + ".json"))
    return Cell(
        name=name, config_name=w["config"], traffic=w["traffic"],
        chips=int(w["chips"]), config=config, mix=mix, app=config["app"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_module(kind: str, name: str, root: str = REPO_ROOT):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: str = REPO_ROOT) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
