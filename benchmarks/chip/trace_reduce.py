"""From a profiler trace to the numbers the per-layer metrics read.

A trace is first flattened (:func:`load_xplane`) to plain lists, which
is also the form the tests record:

    {"device": [[plane, [[module, start_ns, dur_ns], ...],       # XLA Modules
                        [[op, start_ns, dur_ns], ...]], ...],   # XLA Ops
     "host":   [[name, start_ns, dur_ns], ...]}                 # bench/* spans

:func:`reduce_trace` then gives, over the traced span ``bench/traced``:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices; ``window_s``: the span's length;
- ``programs``: for each jitted program (module name without its
  fingerprint), its calls and device seconds, and the same per
  fingerprint, since every ``jax.jit`` of a lambda has one name;
- ``device_ops``: the ten operations that took most device time;
- ``idle_gaps``: the device's idle time by what the host was doing,
  the host span that overlaps each gap most, the ten largest.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Any, Dict, List, Sequence, Tuple

TRACED_SPAN = "bench/traced"
HOST_PREFIX = "bench/"
_FINGERPRINT = re.compile(r"^(.*)\((\d+)\)$")

Interval = Tuple[float, float]


def load_xplane(log_dir: str) -> Dict[str, Any]:
    """Flatten the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith(
                "/device:CPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = [[e.name, e.start_ns, e.duration_ns]
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            ops = [[e.name, e.start_ns, e.duration_ns]
                   for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
            device.append([plane.name, mods, ops])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"device": device, "host": host}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between merged busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_name(module: str) -> Tuple[str, str]:
    m = _FINGERPRINT.match(module)
    return (m.group(1), m.group(2)) if m else (module, "")


def op_name(op: str) -> str:
    """``%fusion.106 = bf16[...] fusion(...)`` → ``fusion.106``."""
    head = op.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def _attribute(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    best, best_overlap, best_len = "(no host span)", 0.0, float("inf")
    for name, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        # the span overlapping most; among equals the innermost
        if ov > best_overlap or (ov == best_overlap and e - s < best_len):
            best, best_overlap, best_len = name, ov, e - s
    return best


def reduce_trace(flat: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    spans = [h for h in flat["host"] if h[0] == TRACED_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {TRACED_SPAN} span, found {len(spans)}")
    lo = float(spans[0][1])
    hi = lo + float(spans[0][2])
    host = [(n, float(s), float(s) + float(d)) for n, s, d in flat["host"]
            if n != TRACED_SPAN and float(s) + float(d) > lo and float(s) < hi]
    if not flat["device"]:
        raise ValueError("the trace holds no device plane")

    busy_total = 0.0
    programs: Dict[str, Dict[str, Any]] = {}
    op_time: Dict[str, float] = collections.defaultdict(float)
    gap_time: Dict[str, float] = collections.defaultdict(float)
    for _plane, mods, ops in flat["device"]:
        op_iv = clip([(float(s), float(s) + float(d)) for _, s, d in ops], lo, hi)
        busy = union(op_iv)
        busy_total += sum(e - s for s, e in busy)
        for g in gaps(busy, lo, hi):
            gap_time[_attribute(g, host)] += g[1] - g[0]
        mod_iv = sorted((float(s), float(s) + float(d), n) for n, s, d in mods)
        starts = [m[0] for m in mod_iv]
        for name, s, d in mods:
            s = float(s)
            if not lo <= s < hi:
                continue
            base, key = program_name(name)
            p = programs.setdefault(base, {"calls": 0, "device_s": 0.0,
                                           "by_key": {}})
            p["calls"] += 1
            p["device_s"] += float(d) / 1e9
            k = p["by_key"].setdefault(key, [0, 0.0])
            k[0] += 1
            k[1] += float(d) / 1e9
        for name, s, d in ops:
            s, d = float(s), float(d)
            if not lo <= s < hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            where = ""
            if i >= 0 and mod_iv[i][0] <= s < mod_iv[i][1]:
                where = program_name(mod_iv[i][2])[0] + "/"
            op_time[where + op_name(name)] += d / 1e9
    n_dev = len(flat["device"])
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_total / n_dev / 1e9,
        "programs": programs,
        "device_ops": [[k, v / n_dev] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n_dev / 1e9] for k, v in sorted(
            gap_time.items(), key=lambda kv: -kv[1])[:top]],
    }


def idle_share(reduced) -> "float | None":
    """Percent of the traced span in which no operation ran."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def split_by_calls(reduced, program: str, calls):
    """Among the compiled variants of ``program`` (one per fingerprint),
    the one run most often, which has to have run ``calls`` times:
    ``((calls, device_s), device_s of all the others)``, or None where
    the trace does not hold it."""
    p = (reduced or {}).get("programs", {}).get(program)
    if not p or not calls:
        return None
    key = max(p["by_key"], key=lambda k: p["by_key"][k][0])
    n, s = p["by_key"][key]
    if abs(n - calls) > 1:
        return None
    rest = sum(v[1] for k, v in p["by_key"].items() if k != key)
    return (n, s), rest
