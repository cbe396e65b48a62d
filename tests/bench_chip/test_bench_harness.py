"""The harness: cells found by name, additions found without edits, no
result without a TPU, and whole runs on the CPU at small size."""

import json
import os
import shutil
import subprocess
from pathlib import Path

import pytest

from benchmarks.chip import harness

import tiny

ROOT = tiny.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_its_files_by_name(name):
    cell = harness.resolve(SPEC, name, ROOT)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    # each per-layer metric moves an end-to-end metric the cell reports
    assert {m["moves"] for m in cell.per_layer} <= e2e
    assert (harness.HERE / f"{cell.config['system']}.py").is_file()
    assert cell.traffic["kind"] == cell.config["system"]


def test_a_new_mix_and_metric_are_found_without_edits(tmp_path):
    here = tmp_path / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.FIXTURES / "tiny-chat.json", here / "traffic" /
                "new-mix.json")
    (here / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx['answer']\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "qwen3-4b-w4.new-mix",
                              "config": "qwen3-4b-w4", "traffic": "new-mix",
                              "chips": 1})
    spec["per_layer"].append({"name": "new.metric", "unit": "%",
                              "workloads": ["qwen3-4b-w4.new-mix"]})
    cell = harness.resolve(spec, "qwen3-4b-w4.new-mix", ROOT, here)
    assert cell.traffic["rate_per_s"] == 40
    assert harness.read_layers(cell, {"answer": 42.0}) == {
        "new.metric": {"value": 42.0, "unit": "%"}}
    with pytest.raises(harness.BenchError):
        harness.resolve(spec, "no-such-cell", ROOT, here)


def _command(cwd: Path):
    cmd = SPEC["command"] + ["--workload", "qwen3-4b-w4.batch", "--seed",
                             str(2**33 + 1), "--seconds", "1", "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_the_command_gives_no_result_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and not out.stdout.strip()


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0 and not out.stdout.strip()


@pytest.mark.parametrize("config,mix", [("qwen3-tiny-w4", "tiny-batch"),
                                        ("qwen3-tiny-w4", "tiny-chat")])
def test_a_traced_run_on_the_cpu(config, mix, tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, config, mix)
    res = tiny.run(cell, monkeypatch, tmp_path, tracing=True)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert {"serve.idle_share", "serve.host_ms_per_step"} <= set(
        res["metrics"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert list(res)[-1] == "check"
    assert not (tmp_path / "scratch" / "trace").exists()


def test_an_untraced_run_reports_the_cells_end_to_end_metrics(
        tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, "stablelm-tiny-w8", "tiny-batch")
    res = tiny.run(cell, monkeypatch, tmp_path)
    assert res["correct"]
    assert set(res["metrics"]) == {"gap_p90_ms", "tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["check"]["served_logit_gap"]["value"] <= \
        res["check"]["served_logit_gap"]["limit"]


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.-]{1,16}$"


def test_benchmark_json_keeps_to_its_format():
    import re

    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
               for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51
    under = lambda f: any(f.startswith(p + "/") for p in SPEC["paths"])
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.match(NAME, c["name"]) and under(c["file"])
        assert all(re.match(NAME, k) for k in c["reduced"])
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert re.match(NAME, w["name"]) and re.match(NAME, w["traffic"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
