"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic in ``traffic/<traffic>.json`` (whose ``driver`` names the module
in ``drivers/`` that runs it), and each metric's reader in
``metrics/<metric>.py``.  With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of a steady part of the window.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``, and
last the ``checks``, each number compared with its limit.  The run exits
non-zero, printing no result, where JAX finds no TPU or too few chips,
or where the system under test is not in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, devices=None, bench=None, bench_dir=harness.HERE, work=None):
    """One run; returns the result line.  The keywords are for tests,
    which drive a run on the CPU in place of the chip: its devices, the
    parsed ``BENCHMARK.json``, the directory its traffic and metric files
    are found in, and a scratch directory."""
    root = harness.ROOT
    if bench is None:
        bench = harness.load_benchmark(root)
    cell = harness.resolve_cell(bench, args.workload, root, bench_dir)
    harness.import_program(root)
    if devices is None:
        devices = harness.require_tpu(cell.chips)
    dev = devices[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    from benchmarks.chip import peaks

    cache_dir = harness.enable_compile_cache()
    counter = harness.CompileCounter()
    spec = harness.RunSpec(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, counter=counter,
        work_dir=harness.work_dir(root if work is None else work),
        peaks=peaks.peaks_for(dev.device_kind),
        devices=devices,
    )
    driver = harness.driver_module(cell.traffic["driver"])
    window = driver.run(spec)
    print(f"[compile cache] {cache_dir}: {json.dumps(counter.snapshot())}",
          flush=True)
    if window.trace is not None:
        progs = {k: [v["calls"], v["device_s"], v["by_key"]]
                 for k, v in window.trace["programs"].items()}
        print(f"[trace] programs {json.dumps(progs)}", flush=True)
    for key, value in window.notes.items():
        print(f"[note] {key} {json.dumps(value)}", flush=True)
    print(f"[window] compilations inside the window: "
          f"{window.notes.get('window_compiles')}", flush=True)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = harness.compute_metrics(window, wanted, bench_dir)
    line = harness.result_line(window, metrics, dict(window.memory))
    harness.print_checks(window.checks)
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        line = run(args)
    except harness.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
