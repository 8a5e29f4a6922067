"""``BENCHMARK.json`` and the files it names, and ``bench/run.py``'s
refusal to run without a TPU or outside the repository."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        for k in ("source", "assumed", "reduced", "chips", "guarantee"):
            assert k in cfg, (c["name"], k)
        assert os.path.exists(os.path.join(ROOT, "bench", "apps",
                                           cfg["app"] + ".py"))
    for w in SPEC["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = harness.per_layer(SPEC, w["name"])
        assert layers, w["name"]
        assert all(m["moves"] in e2e for m in layers), w["name"]


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_readers_find_nothing_in_an_empty_run():
    run = harness.RunData(cell={"name": "x"}, cfg={}, mix={},
                          device_kind="TPU v5 lite")
    names = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "bench",
                                                           "metrics"))
                   if f.endswith(".py"))
    assert {m["name"] for m in SPEC["per_layer"]} <= set(names)
    for name in names:
        assert harness.metric_reader(name)(run) is None, name


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_a_tpu(tmp_path, where):
    cwd = ROOT
    if where == "alone":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        shutil.copytree(os.path.join(ROOT, "bench"),
                        os.path.join(cwd, "bench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "counting.flood",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
