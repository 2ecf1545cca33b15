"""BENCHMARK.json against the benchmark's contract, discovery of a cell's
pieces by file name alone, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness as H

ROOT = Path(H.__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_run_seconds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits in 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    assert all(not p.startswith("/") and ".." not in p
               for p in BENCH["paths"] + BENCH["command"][1:])


def test_names_units_and_entry_keys():
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"}}
    for kind, keys in allowed.items():
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
        for e in BENCH[kind]:
            assert set(e) == keys, e
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    metric_keys = {"name", "unit", "better", "source"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == metric_keys | {"bound"}, m
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == metric_keys | {"layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_finds_its_pieces_and_reports_enough():
    chips4 = sum(c["chips"] == 4 for c in BENCH["workloads"])
    assert chips4 <= max(1, len(BENCH["workloads"]) // 2)
    for cell in BENCH["workloads"]:
        config = H.load_config(cell["config"])
        traffic = H.load_traffic(cell["traffic"])
        driver = H.load_driver(config["driver"])
        assert traffic["kind"] in driver.TRAFFIC_KINDS
        e2e = {m["name"] for m in H.cell_metrics(BENCH, cell["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = H.cell_metrics(BENCH, cell["name"], "per_layer")
        assert per_layer
        for m in per_layer:
            assert m["moves"] in e2e
            assert callable(H.load_metric_reader(m["name"]))
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_discovery_by_file_name_alone(tmp_path):
    """A new configuration, traffic mix and metric are found by their
    names once their files exist: no existing file is edited."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "configs" / "new_model.json").write_text(
        json.dumps({"driver": "serve", "hidden_size": 8}))
    (bench_dir / "traffic" / "new_mix.json").write_text(
        json.dumps({"kind": "closed_loop", "pool": 3}))
    (bench_dir / "metrics" / "new.metric.py").write_text(
        "def read(view):\n    return 42.0\n")
    assert H.load_config("new_model", bench_dir)["hidden_size"] == 8
    assert H.load_traffic("new_mix", bench_dir)["pool"] == 3
    assert H.load_metric_reader("new.metric", bench_dir)(None) == 42.0
    bench = {"end_to_end": [{"name": "x", "moves": None},
                            {"name": "y", "workloads": ["other"]}],
             "per_layer": [{"name": "new.metric", "moves": "x",
                            "workloads": ["c"]},
                           {"name": "all", "moves": "x"},
                           {"name": "elsewhere", "moves": "y"}]}
    assert [m["name"] for m in H.cell_metrics(bench, "c", "per_layer")] \
        == ["new.metric", "all"]
    assert all(p.read_bytes() == b for p, b in before.items())
    with pytest.raises(H.BenchError):
        H.load_config("missing", bench_dir)


def test_unknown_device_kind_has_no_default_peaks():
    with pytest.raises(H.BenchError, match="no default peaks"):
        H.load_peaks("TPU v9 imaginary")
    assert H.load_peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
