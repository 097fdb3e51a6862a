"""What every cell shares: finding its files by name, the device, the
compile cache and its counter, the checks and the result line.

Nothing here imports the program at module level, so a checkout that
holds only ``BENCHMARK.json`` and the benchmark's own directory fails in
:func:`import_program`, before any result is printed.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: BENCHMARK.json and src/ live here


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file, ...)."""


# ------------------------------------------------------------- the files


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve_cell(bench: Dict[str, Any], workload: str,
                 root: Path = ROOT, bench_dir: Path = HERE) -> Cell:
    """Find a workload's configuration, traffic and metrics by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, bench_dir: Path = HERE) -> Callable:
    """``metrics/<name>.py``'s ``read(window)``: a number, or None where
    the window holds nothing for it to read."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_module(name: str):
    return importlib.import_module(f"benchmarks.chip.drivers.{name}")


# --------------------------------------------------------------- device


def require_tpu(chips: int):
    """JAX's devices, which must be TPUs, at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"this benchmark measures a TPU; JAX's first device is "
            f"{devs[0].platform!r}"
        )
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def import_program(root: Path = ROOT) -> None:
    """Import ``repro`` from this checkout's ``src`` and nowhere else."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"the system under test is not at {src}/repro")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro

    where = sorted({os.path.abspath(p) for p in repro.__path__})
    if where != [str(src / "repro")]:
        raise BenchError(f"repro imported from {where}, not from {src}")


class CompileCounter:
    """Counts XLA compilations and persistent-cache reads, so the window
    can show it compiled nothing."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @property
    def programs(self) -> int:
        """Programs compiled or loaded from the persistent cache: either
        means a new program was made ready."""
        return self.compiles + self.cache_hits

    def snapshot(self) -> Dict[str, int]:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def enable_compile_cache() -> str:
    """The program's own cache directory rule (``JAX_COMPILATION_CACHE_DIR``
    or a fixed directory in the checkout), with every program kept: JAX
    otherwise skips those that compile in under a second, and a warm run
    would compile them again."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devs, chips: int) -> Dict[str, Any]:
    used = devs[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": max(peaks),
    }


# ---------------------------------------------------------------- checks


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where ``value <= limit`` (and the value is a number)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return isinstance(self.value, (int, float)) and math.isfinite(
            self.value
        ) and self.value <= self.limit


# ---------------------------------------------------------------- window


@dataclasses.dataclass
class Window:
    """What a driver hands back: the counts and times of its window, the
    trace's reduction where it traced, and its checks."""

    setup_s: float
    attempted: int
    failed: int
    counts: Dict[str, float]
    checks: List[Check]
    # the device as JAX reports it, read after the window and before any
    # reference runs (a process's peak never falls again)
    memory: Dict[str, Any]
    trace: Optional[Dict[str, Any]] = None
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunSpec:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    counter: Any
    work_dir: Path
    peaks: Dict[str, float]
    # set by tests: run the drivers on the CPU with these devices
    devices: Any = None


def work_dir(root: Path = ROOT) -> Path:
    """Scratch space for the run's data files: inside the checkout, at a
    fixed path (git-ignored), emptied by each run."""
    d = Path(root) / ".bench_work"
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir()
    return d


def compute_metrics(window: Window, metrics: List[Dict[str, Any]],
                    bench_dir: Path = HERE) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        value = metric_reader(m["name"], bench_dir)(window)
        if value is None:
            continue
        if not (isinstance(value, (int, float)) and math.isfinite(value)):
            raise BenchError(f"metric {m['name']} read {value!r}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(window: Window, metrics: Dict[str, Any],
                device: Dict[str, Any]) -> Dict[str, Any]:
    correct = bool(window.checks) and all(c.ok for c in window.checks)
    line: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(window.attempted),
        "failed": int(window.failed),
        "metrics": metrics,
        "device": device,
    }
    if window.trace is not None:
        line["device"]["busy_s"] = window.trace["busy_s"]
        line["device"]["window_s"] = window.trace["window_s"]
        line["breakdown"] = {
            "device_ops": window.trace["device_ops"],
            "idle_gaps": window.trace["idle_gaps"],
        }
    line["checks"] = {
        c.name: {"value": c.value, "limit": c.limit} for c in window.checks
    }
    return line


def print_checks(checks: List[Check]) -> None:
    for c in checks:
        verdict = "ok" if c.ok else "FAILED"
        print(f"check {c.name} {c.value!r} limit {c.limit!r} {verdict}",
              file=sys.stderr, flush=True)
