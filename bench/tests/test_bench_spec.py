"""Cells, mixes, apps and metrics are found by name; the peaks table has
no default."""

import json
import os
import shutil

import pytest

from _sub import BENCH, last_json, run
from harness import spec

ROOT = os.path.dirname(BENCH)


def test_every_cell_resolves_to_its_files():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"])
        assert cell.name == f"{cell.config_name}.{cell.traffic}"
        assert os.path.exists(os.path.join(BENCH, "apps", cell.app + ".py"))
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.load_module("metrics", m["name"]).read)
        assert set(cell.config["limits"])
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")


def test_peaks_table_has_no_default():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")


def test_a_new_config_and_mix_are_found_by_name(tmp_path):
    """A later change adds a cell as new files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    bench = spec.benchmark()
    cfg = spec.load_json(os.path.join(BENCH, "configs", "heat3d-256.json"))
    cfg["rehearsal"] = {"nx": 18, "ny": 18, "nz": 18}
    (root / "bench" / "configs" / "heat3d-tiny.json").write_text(json.dumps(cfg))
    mix = spec.load_json(os.path.join(BENCH, "mixes", "plain.json"))
    mix["bumps"] = 2
    (root / "bench" / "mixes" / "quick.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": "heat3d-tiny", "source": "test",
                             "file": "bench/configs/heat3d-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "heat3d-tiny.quick",
                               "config": "heat3d-tiny", "traffic": "quick",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "step_ms":
            m["workloads"].append("heat3d-tiny.quick")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = last_json(run(str(root / "bench" / "run.py"), "--workload",
                        "heat3d-tiny.quick", "--seed", 5, "--seconds", 1,
                        "--rehearsal", cwd=str(root)))
    assert res["correct"] is True
    assert res["would_report"] == ["setup_s", "step_ms"]
