"""The program's own spans in a chip run.

``repro.obs.trace`` records spans where the program does its work into
per-thread rings on ``perf_counter_ns`` and, while it records, mirrors
each span onto the profiler's clock.  This module holds what the
benchmark reads of them:

- :func:`recording`: the ring on for a cell's window;
- :func:`reduce_events`: per span name, its count, total seconds and self
  seconds (its duration less what its children on its thread cover);
- :func:`host_gaps`: the time from one span's end to the next one's
  start, for a span that repeats (``train/step``);
- :func:`load_program` and :func:`program_gaps`: the program spans of a
  profiler trace, and the device's idle time in the traced span put down
  to the innermost program span of the cell's loop thread.

Run as a script, it runs one cell as ``run.py`` does, with the ring on
through the window, and adds a ``program`` section to the result line:

    python3 benchmarks/chip/program_spans.py --workload <cell> \\
        --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` its end-to-end metrics are those of a run that
records, to set against a plain run's; with ``--trace 1`` the section
also holds ``program_gaps`` for the traced slice.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run  # noqa: E402  (first: its clock starts set-up)
from benchmarks.chip import harness, stats, trace_reduce  # noqa: E402

# the prefixes of the spans the program records
PREFIXES = ("serve/", "train/", "svm/", "pipeline/", "storage/", "cache/",
            "prefetch/", "remote/")
NO_SPAN = "(no program span)"


@contextlib.contextmanager
def recording():
    """The program's ring on for the block; yields the recorder, which
    stays drainable after the block."""
    from repro.obs import trace

    rec = trace.enable()
    try:
        yield rec
    finally:
        trace.disable()


def reduce_events(events: Sequence[Dict[str, Any]]
                  ) -> Dict[str, Dict[str, float]]:
    """Per span name of the ring's drained events (Chrome form, times in
    microseconds): ``count``, ``total_s`` and ``self_s``, each span's
    duration less the durations of the spans directly inside it on its
    thread."""
    out: Dict[str, Dict[str, float]] = {}
    by_thread = collections.defaultdict(list)
    for e in events:
        if e["ph"] == "X":
            by_thread[e["tid"]].append(e)

    def close(frame) -> None:
        name, dur, inner = frame[0], frame[2], frame[3]
        s = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += dur / 1e6
        s["self_s"] += (dur - inner) / 1e6

    for evs in by_thread.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[list] = []   # [name, end, dur, time of direct children]
        for e in evs:
            while stack and stack[-1][1] <= e["ts"]:
                close(stack.pop())
            if stack:
                stack[-1][3] += e["dur"]
            stack.append([e["name"], e["ts"] + e["dur"], e["dur"], 0.0])
        while stack:
            close(stack.pop())
    return out


def host_gaps(events: Sequence[Dict[str, Any]], name: str) -> List[float]:
    """Seconds from each ``name`` span's end to the next one's start."""
    spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                   if e["ph"] == "X" and e["name"] == name)
    return [(s2 - e1) / 1e6 for (_, e1), (s2, _) in zip(spans, spans[1:])]


def load_program(log_dir: str) -> List[list]:
    """The program's spans in the newest ``.xplane.pb`` under ``log_dir``:
    ``[name, start_ns, dur_ns, lane]``, a lane being one host thread."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            lane = f"{plane.name}#{i}"
            for e in ln.events:
                if e.name.startswith(PREFIXES):
                    out.append([e.name, e.start_ns, e.duration_ns, lane])
    return out


def _innermost(spans: Sequence[Tuple[float, float, str]]):
    """Disjoint ``(start, end, name)`` segments of one thread's nested
    spans, each named for the innermost span open over it."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    order = sorted(spans, key=lambda x: (x[0], -(x[1] - x[0])))
    segs, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i][0] <= a:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            segs.append((a, b, stack[-1][2]))
    return segs


def program_gaps(flat: Dict[str, Any], program: Sequence[list],
                 top: int = 10) -> List[list]:
    """The device's idle time in the traced span by program span: each
    part of an idle gap goes to the innermost span open over it on the
    loop's thread (the thread whose spans cover most of the traced
    span), else to ``(no program span)``; seconds, the mean over the
    devices, the ``top`` largest."""
    traced = [h for h in flat["host"] if h[0] == trace_reduce.TRACED_SPAN]
    if len(traced) != 1:
        raise ValueError(f"expected one {trace_reduce.TRACED_SPAN} span")
    lo = float(traced[0][1])
    hi = lo + float(traced[0][2])
    lanes = collections.defaultdict(list)
    for name, s, d, lane in program:
        lanes[lane].append((float(s), float(s) + float(d), name))

    def covered(spans) -> float:
        return sum(e - s for s, e in trace_reduce.union(
            trace_reduce.clip([(s, e) for s, e, _ in spans], lo, hi)))

    loop = max(lanes.values(), key=covered) if lanes else []
    segs = _innermost(loop)
    ends = [e for _, e, _ in segs]
    idle: Dict[str, float] = collections.defaultdict(float)
    for _plane, _mods, ops in flat["device"]:
        busy = trace_reduce.union(trace_reduce.clip(
            [(float(s), float(s) + float(d)) for _, s, d in ops], lo, hi))
        for g0, g1 in trace_reduce.gaps(busy, lo, hi):
            left = g1 - g0
            k = bisect.bisect_right(ends, g0)
            while k < len(segs) and segs[k][0] < g1:
                part = min(segs[k][1], g1) - max(segs[k][0], g0)
                idle[segs[k][2]] += part
                left -= part
                k += 1
            idle[NO_SPAN] += left
    n_dev = len(flat["device"])
    return [[k, v / n_dev / 1e9] for k, v in sorted(
        idle.items(), key=lambda kv: -kv[1])[:top] if v > 0]


# ------------------------------------------------------------- one run


class Probe:
    """Hooks into one run of a cell: the ring on through the window of
    the cell's module in ``drivers/``, the engine's counters at the
    window's close (where those modules read the device's memory), and
    the program spans of the profiler trace beside the benchmark's
    reduction of it."""

    def __init__(self) -> None:
        self.rec = None
        self.session = None
        self.events: List[Dict[str, Any]] = []
        self.close_us: Optional[float] = None
        self.at_close: Dict[str, int] = {}
        self.waits: Optional[List[Optional[float]]] = None
        self.traces: List[Tuple[Dict[str, Any], List[list]]] = []

    @contextlib.contextmanager
    def hooks(self, driver):
        real_window = driver.Session.window
        real_info = harness.device_info
        real_load = trace_reduce.load_xplane

        def window(session):
            self.session = session
            with recording() as rec:
                self.rec = rec
                out = real_window(session)
            self.events = rec.drain()
            self.queue_waits(session, out)
            return out

        def device_info(*a, **k):
            if self.rec is not None and self.close_us is None:
                self.close_us = (time.perf_counter_ns() - self.rec.t0_ns) / 1e3
                eng = getattr(self.session, "engine", None)
                if eng is not None:
                    self.at_close = {
                        "slot_steps": getattr(eng, "slot_steps", None),
                        "decode_steps": eng.decode_steps}
            return real_info(*a, **k)

        def load_xplane(log_dir):
            flat = real_load(log_dir)
            self.traces.append((flat, load_program(log_dir)))
            return flat

        with mock.patch.object(driver.Session, "window", window), \
                mock.patch.object(harness, "device_info", device_info), \
                mock.patch.object(trace_reduce, "load_xplane", load_xplane):
            yield self

    def queue_waits(self, session, out) -> None:
        """Each request due in the window: its admission less its due
        time on the window's clock, None where it was never admitted."""
        eng = getattr(session, "engine", None)
        if eng is None:
            return
        admitted = {c.rid: getattr(c, "admitted", None)
                    for c in eng.completions}
        admitted.update({s.request.rid: s.admitted for s in eng.slots.values()})
        due = session.requests[:out["counts"]["submitted"]]
        self.waits = [admitted[r["rid"]] - r["due"]
                      if admitted.get(r["rid"]) is not None else None
                      for r in due]

    def summary(self) -> Dict[str, Any]:
        """The section the run's line gains."""
        close = math.inf if self.close_us is None else self.close_us
        evs = [e for e in self.events if e["ts"] <= close]
        spans = reduce_events(evs)
        out: Dict[str, Any] = {"dropped": self.rec.dropped if self.rec else None,
                               "spans": spans}
        if self.waits:
            p95 = stats.tail(self.waits, 95)
            out["serve_queue_wait_p95_ms"] = (
                1e3 * p95 if math.isfinite(p95) else None)
        steps = self.at_close.get("decode_steps")
        if steps and self.at_close.get("slot_steps") is not None:
            out["serve_batch_occupancy"] = self.at_close["slot_steps"] / steps
        if "serve/step" in spans:
            host = sum(spans[n]["self_s"] for n in (
                "serve/step", "serve/admit", "serve/emit") if n in spans)
            out["serve_step_host_ms"] = 1e3 * host / spans["serve/step"]["count"]
        if "svm/put" in spans:
            out["svm_put_ms"] = (1e3 * spans["svm/put"]["total_s"]
                                 / spans["svm/margins"]["count"])
        gaps = host_gaps(evs, "train/step")
        if gaps:
            out["train_host_gap_ms"] = 1e3 * sum(gaps) / len(gaps)
        if self.traces:
            out["program_gaps"] = program_gaps(*self.traces[-1])
        return out


def main(argv=None) -> int:
    args = run.parse_args(argv)
    try:
        bench = harness.load_benchmark()
        cell = harness.resolve_cell(bench, args.workload)
        driver = harness.driver_module(cell.traffic["driver"])
        probe = Probe()
        with probe.hooks(driver):
            line = run.run(args, bench=bench)
    except harness.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    line["program"] = probe.summary()
    print(f"[note] trace_dropped {line['program']['dropped']}", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
