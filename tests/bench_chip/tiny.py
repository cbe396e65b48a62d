"""Cells at a size the CPU test suite can run, driven by the harness with
its look for a chip skipped."""

import json
import shutil
from pathlib import Path

from benchmarks.chip import harness, trace, work

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).parent / "fixtures"


def cell(tmp: Path, config: str, mix: str, chips: int = 1):
    """Resolve a one-cell benchmark over a copy of the harness with the
    fixture ``mix`` dropped into its traffic directory."""
    here = tmp / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIXTURES / f"{mix}.json", here / "traffic" / f"{mix}.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"{config}.{mix}"
    spec["configs"] = [{"name": config,
                        "file": f"tests/bench_chip/fixtures/{config}.json"}]
    spec["workloads"] = [{"name": name, "config": config, "traffic": mix,
                          "chips": chips}]
    system = json.loads((FIXTURES / f"{config}.json").read_text())["system"]
    mix_doc = json.loads((FIXTURES / f"{mix}.json").read_text())
    keep = {"closed": {"gap_p90_ms", "tok_s", "setup_s"},
            "open": {"gap_p90_ms", "ttft_p90_ms", "setup_s"}}[mix_doc["loop"]]
    layer = f"{system}."
    spec["end_to_end"] = [dict(m, workloads=[name])
                          for m in spec["end_to_end"] if m["name"] in keep]
    spec["per_layer"] = [dict(m, workloads=[name]) for m in spec["per_layer"]
                         if m["name"].startswith(layer)]
    return harness.resolve(spec, name, ROOT, here)


def run(c, monkeypatch, tmp: Path, *, seed=2**31 + 11, seconds=0.4,
        tracing=False):
    """One run, the CPU standing in for the chip: peaks from a fixture,
    device operations read from the CPU's XLA threads."""
    monkeypatch.setattr(work, "PEAKS", FIXTURES / "cpu-peaks.json")
    monkeypatch.setattr(trace, "DEVICE_PLANE", r"^/host:CPU$")
    monkeypatch.setattr(trace, "OP_LINE", r"^tf_XLA")
    if c.traffic.get("loop") == "closed":
        monkeypatch.setitem(c.traffic, "lead_in_steps", 10)
    device = harness.device_info(c.chips, require_tpu=False)
    import time

    return harness.run_cell(c, seed, seconds, tracing, time.perf_counter(),
                            device, ROOT, tmp / "scratch")
