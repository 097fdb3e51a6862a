"""Readings the limits of ``correct`` are set from, and the serving knee.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 11,12,13 --seconds 4 [--control] [--set key=json ...] \
        [--sweep key=v1,v2,...] [--no-check]

Runs the cell's set-up, window and checks once for each seed, in one
process that holds the chip, and prints one JSON line per seed: the
end-to-end metrics, each number compared, and with ``--control`` the
same numbers read from the control and from the faults the driver plants
in the reference.  ``--set rate=30`` overrides a key of the cell's
traffic; ``--sweep rate=20,30,40`` makes one pass per value, which is
how the serving knee is found (with ``--no-check``).
The benchmark's own runs never run the control; this tool is for
setting and re-checking the limits in the traffic files.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--no-check", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--sweep", default="",
                    help="key=v1,v2,...: one pass over the seeds per value")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    root = harness.ROOT
    cell = harness.resolve_cell(harness.load_benchmark(root), args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        cell.traffic[key] = json.loads(value)
    harness.import_program(root)
    devices = harness.require_tpu(cell.chips)
    from benchmarks.chip import peaks

    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    driver = harness.driver_module(cell.traffic["driver"])
    wanted = [m for m in cell.end_to_end if m["name"] != "setup_s"]
    key, values = (args.sweep.split("=", 1) + [""])[:2] if args.sweep else (
        None, "null")
    runs = [(v, int(s)) for v in values.split(",")
            for s in args.seeds.split(",")]
    for value, seed in runs:
        if key is not None:
            cell.traffic[key] = json.loads(value)
        t0 = time.perf_counter()
        spec = harness.RunSpec(
            cell=cell, seed=seed, seconds=args.seconds, trace=False,
            t_start=t0, counter=counter, work_dir=harness.work_dir(root),
            peaks=peaks.peaks_for(devices[0].device_kind), devices=devices)
        ev = driver.evidence(spec)
        w = ev["window"]
        window = harness.Window(
            setup_s=w["setup_s"], attempted=0, failed=0, counts=w["counts"],
            checks=[], memory=w["memory"])
        out = {"seed": seed, "sweep": {key: cell.traffic[key]} if key else {},
               "metrics": harness.compute_metrics(window, wanted),
               "window_compiles": w["window_compiles"],
               "memory_peak_bytes": w["memory"]["memory_peak_bytes"]}
        if "queue" in w["counts"]:
            out["queue"] = w["counts"]["queue"]
        if not args.no_check:
            out["checks"] = {c.name: c.value for c in driver.checks(spec, ev)}
        if args.control:
            out["control"] = {name: {c.name: c.value for c in found}
                              for name, found in driver.control(spec, ev).items()}
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
