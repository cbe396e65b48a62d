"""Cells, files found by name, the device check and the result line.

A cell of ``BENCHMARK.json`` names a configuration (its ``file``), a
traffic mix (``benchmarks/chip/traffic/<traffic>.json``) and, through the
metrics that list it, the per-layer readers
(``benchmarks/chip/metrics/<metric>.py``).  Nothing here names a cell,
a model or a metric: a later change adds one by adding files and entries.
The configuration's ``system`` names the runner module beside this file
(``serve.py``) that runs it.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
STATE = ".bench_chip"          # inside the checkout, ignored by git


class BenchError(Exception):
    """The cell cannot be run here: a missing file, chip or entry."""


class BookError(BenchError):
    """The program did something the harness's model of it does not
    allow, so the run's numbers cannot be taken."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_reader(path: Path):
    """A per-layer metric's reader: the module's ``read(ctx)``."""
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics._{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(spec: dict, name: str, root: Path = ROOT,
            here: Path = HERE) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{name}: no configuration {w['config']!r}")
    cfg_path = Path(root) / configs[w["config"]]["file"]
    mix_path = here / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, mix_path):
        if not p.is_file():
            raise BenchError(f"{name}: {p} is missing")
    from . import traffic

    per_layer = _for_cell(spec["per_layer"], name)
    readers = {}
    for m in per_layer:
        path = here / "metrics" / f"{m['name']}.py"
        if not path.is_file():
            raise BenchError(f"{name}: no reader {path}")
        readers[m["name"]] = load_reader(path)
    return Cell(name=name, chips=int(w["chips"]),
                config=json.loads(cfg_path.read_text()),
                traffic=traffic.load(mix_path),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=per_layer, readers=readers)


def state_dir(root: Path = ROOT) -> Path:
    return Path(root) / STATE


def enable_compile_cache(root: Path = ROOT) -> Path:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program."""
    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or state_dir(root) / "jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; a run without a TPU, or with fewer
    chips than the cell asks for, stops here."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {d.platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts, while entered, the traces, the executables built, and how
    many of those the persistent cache held (``compiles`` are the rest:
    real compilations)."""

    _listening: list["CompileCounter"] = []

    def __init__(self) -> None:
        self.traces = self.builds = self.cache_hits = 0

    @property
    def compiles(self) -> int:
        return self.builds - self.cache_hits

    def __str__(self) -> str:
        return (f"{self.traces} trace(s), {self.compiles} compile(s), "
                f"{self.cache_hits} executable(s) from the compile cache")

    @classmethod
    def _on_duration(cls, event: str, duration: float, **_) -> None:
        for c in cls._listening:
            if event == "/jax/core/compile/jaxpr_trace_duration":
                c.traces += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                c.builds += 1

    @classmethod
    def _on_event(cls, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            for c in cls._listening:
                c.cache_hits += 1

    def __enter__(self):
        import jax
        from jax._src import monitoring

        if self._on_duration not in \
                monitoring.get_event_duration_listeners():
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
        CompileCounter._listening.append(self)
        return self

    def __exit__(self, *exc) -> None:
        CompileCounter._listening.remove(self)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class Tracer:
    """The profiler over a window, or nothing when ``path`` is None.

    The Python tracer stays off: it records every Python call, which
    inflates the host time between steps that the readers measure, and
    its stop takes tens of seconds.  Device operations and the harness's
    own annotations are kept."""

    def __init__(self, path: Path | None) -> None:
        self.path = None if path is None else Path(path) / "trace"

    def __enter__(self):
        if self.path is not None:
            import jax

            shutil.rmtree(self.path, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.path),
                                     profiler_options=options)
        return self

    def __exit__(self, *exc) -> None:
        if self.path is not None:
            import jax

            jax.profiler.stop_trace()

    def reduced(self):
        from . import trace

        try:
            return trace.load(trace.find_xplane(self.path))
        finally:
            shutil.rmtree(self.path, ignore_errors=True)


def runner(system: str):
    """The module that runs a configuration's ``system``: ``<system>.py``
    beside this file."""
    if not (system.isidentifier() and (HERE / f"{system}.py").is_file()):
        raise BenchError(f"no runner for system {system!r}")
    return importlib.import_module(f"{__package__}.{system}")


def read_layers(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]](ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(check: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(v["value"] <= v["limit"] for v in check.values())


def run_cell(cell: Cell, seed: int, seconds: float, tracing: bool,
             t_process: float, device: dict, root: Path = ROOT,
             scratch: Path | None = None, control: bool = False) -> dict:
    """One run of ``cell``: set-up, window, check.  Returns the result
    object that the last line of standard output carries.  ``scratch``
    (default ``.bench_chip/run`` in the checkout) holds the trace.

    With ``control``, the check judges the control in the program's place
    (the runner's ``control_check``), so a sound check reads ``correct``
    false; the program's own check of the same run is kept beside it as
    ``program_check``."""
    from . import trace, work

    scratch = scratch or state_dir(root) / "run"
    out = runner(cell.config["system"]).run(
        cell, seed, seconds, tracing, t_process, root, scratch,
        control=control)
    check = out["control_check"] if control else out["check"]
    correct = judge(check)
    device = dict(device, memory_peak_bytes=out["peak"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if tracing:
        ctx = dict(out["layer_ctx"], peak=work.peaks(device["kind"]))
        tr = ctx["trace"]
        devices = sorted(tr.ops)[:cell.chips]
        window = tr.annotations("bench.window")
        if not devices or not window:
            raise BenchError("the trace holds no device operations or no "
                             "window annotation")
        lo, hi = window[0].start, window[0].end
        ctx.update(devices=devices, window=(lo, hi))
        busy = [trace.busy(tr.ops[d], lo, hi) for d in devices]
        device.update(busy_s=sum(busy) / len(busy) * 1e-9,
                      window_s=(hi - lo) * 1e-9)
        result["metrics"] = read_layers(cell, ctx)
        ops0 = tr.ops[devices[0]]
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace.top_ops(ops0, lo, hi)],
            "idle_gaps": [list(x) for x in trace.idle_gaps(
                ops0, tr.host, lo, hi)]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = out["metrics"].get(m["name"])
            if value is None:
                raise BenchError(f"{cell.name}: no value for {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
    result["device"] = device
    if control:
        result["program_correct"] = judge(out["check"])
        result["program_check"] = out["check"]
    for name, v in check.items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    result["check"] = check
    return result
