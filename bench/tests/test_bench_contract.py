"""BENCHMARK.json is well formed and every name it holds resolves to the
files the harness finds it by."""
import json
import re

import pytest

import tiny

BENCH = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys():
    assert set(BENCH) == TOP
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_full_check_fits_its_time():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_end_to_end_bounds():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(w):
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    c = cfgs[w["config"]]
    assert c["file"].startswith("bench/")
    cfg = json.loads((tiny.ROOT / c["file"]).read_text())
    for key in c["reduced"]:
        side, k = key.split(".")
        assert k in cfg[side]
    assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    limits = json.loads((tiny.BENCH / "limits" / f"{w['name']}.json")
                        .read_text())["limits"]
    assert set(limits) == {"served_gap_max", "route_mismatch",
                           "private_fused", "cloud_replies_lost",
                           "never_served"}


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").exists()


def test_four_chip_cells_are_few():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_no_result_without_the_program_or_a_chip(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, on a host
    whose JAX finds no accelerator, a run exits non-zero and prints no
    result line."""
    import os
    import shutil
    import subprocess
    import sys
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "fixtures"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
