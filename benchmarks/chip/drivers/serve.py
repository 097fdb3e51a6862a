"""Serving window: the program's ``ServeEngine`` driven in an open loop on
the wall clock.

Set-up makes the weights from the seed on the device, the feature store
behind the engine's request-stream cache, and the engine (its arena and
its warm-up, which compiles its programs).  The traffic is drawn from the
seed before the window opens: every seed gets the same set of prompt
lengths, output lengths and gaps between arrivals (quantiles of the
mix's distributions), in an order of its own.

The window submits each request when it is due, through a clock that
reads seconds since the window opened, and calls ``step()`` while the
engine has work.  A request's time to first token runs from its due
time to the end of the ``step()`` that put its first token on the host.
At the close the window's tokens are counted; then the engine runs on,
with nothing more submitted, until every request due in the window has
its first token (a minute at most), so a late one counts as late.  A
sample of finished requests, the longest among them, is then checked
against the reference's full forward pass.
"""
from __future__ import annotations

import collections
import gc
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.chip import data, harness, tracing, weights
from benchmarks.chip.drivers.train import check_layout, program_config
from benchmarks.chip.reference import granite as ref

DRAIN_S = 60.0  # how long the engine may run on after the close


class WallClock:
    """The engine's clock: seconds since the window opened.  ``advance``
    is the step clock's and has nothing to do here."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance(self, dt: float = 1.0) -> None:
        pass


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                      order: np.ndarray) -> np.ndarray:
    """``n`` lengths at the quantiles of a log-normal, clipped, in ``order``."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(np.int64)
    return x[order]


def traffic(seed: int, seconds: float, t: Dict[str, Any], vocab: int
            ) -> List[Dict[str, Any]]:
    """The requests due in a window of ``seconds``: Poisson arrivals at
    ``rate`` (the gaps at the exponential's quantiles), log-normal prompt
    and output lengths, and Zipf-popular feature ids."""
    from repro.serve.request import zipf_probabilities

    n = max(1, int(round(t["rate"] * seconds)))
    g = data.rng(seed, 5)
    gaps = -np.log1p(-_quantiles(n)) / t["rate"]
    gaps = gaps[g.permutation(n)] * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    p = t["prompt"]
    o = t["output"]
    plen = lognormal_lengths(n, p["median"], p["sigma"], p["min"], p["max"],
                             g.permutation(n))
    olen = lognormal_lengths(n, o["median"], o["sigma"], o["min"], o["max"],
                             g.permutation(n))
    f = t["features"]
    fp = zipf_probabilities(f["records"], f["zipf"])
    out = []
    for i in range(n):
        out.append({
            "rid": i, "due": float(due[i]),
            "prompt": g.integers(1, vocab, size=int(plen[i])).astype(np.int32),
            "max_new": int(olen[i]),
            "features": g.choice(f["records"], size=f["per_request"],
                                 p=fp).astype(np.int64),
        })
    return out


class Session:
    """One engine from set-up to the end of its window."""

    def __init__(self, spec: harness.RunSpec):
        from repro.data.synthetic import make_classification_dataset
        from repro.serve.engine import ServeEngine
        from repro.serve.reuse import RequestStreamCache
        from repro.storage.record_store import RecordStore

        self.spec = spec
        c, t = spec.cell.config, spec.cell.traffic
        self.c, self.t = c, t
        self.cfg = program_config(c)
        check_layout(self.cfg, c)
        f = t["features"]
        path = str(spec.work_dir / "features.rrec")
        make_classification_dataset(path, f["records"], dim=f["dim"],
                                    seed=spec.seed)
        self.store = RecordStore(path)
        self.fcache = RequestStreamCache(
            self.store, budget_bytes=int(f["tier_fraction"] * f["records"]
                                         * self.store.record_size),
            policy=f["eviction_policy"])
        self.clock = WallClock()
        self.engine = ServeEngine(
            self.cfg, weights.make_params(ref.layout(c), c, spec.seed),
            max_batch=t["slots"], prompt_capacity=t["prompt"]["max"],
            max_new_tokens=t["output"]["max"], feature_cache=self.fcache,
            clock=self.clock)
        self.engine.warmup()
        self.requests = traffic(spec.seed, spec.seconds, t, c["vocab_size"])

    def _stamp(self, first: Dict[int, float], seen: int) -> int:
        now = self.clock.now()
        for s in self.engine.slots.values():
            first.setdefault(s.request.rid, now)
        done = self.engine.completions
        for comp in done[seen:]:
            first.setdefault(comp.rid, now)
        return len(done)

    def _wrap_programs(self) -> None:
        """Host spans around the engine's device programs (traced runs)."""
        eng = self.engine

        def spanned(name, fn):
            def call(*a, **k):
                with tracing.span(name):
                    return fn(*a, **k)
            return call

        eng._prefill = spanned("prefill", eng._prefill)
        eng._write_slot = spanned("write_slot", eng._write_slot)
        eng._decode = spanned("decode", eng._decode)

    def window(self) -> Dict[str, Any]:
        from repro.serve.request import Request

        spec, eng = self.spec, self.engine
        seconds = spec.seconds
        tracer = tracing.Tracer(spec.work_dir / "trace") if spec.trace else None
        if tracer is not None:
            self._wrap_programs()
        t_on, t_len = tracing.trace_plan(seconds)
        pending = collections.deque(self.requests)
        first: Dict[int, float] = {}
        late: List[float] = []
        seen = 0
        traced_at: Dict[str, int] = {}
        tracing_on = traced = False
        compiles = spec.counter.programs
        tokens0 = eng.generated_tokens
        queue: List[List[float]] = []   # the backlog, eight times a window
        setup_s = time.perf_counter() - spec.t_start
        self.clock.start()
        while True:
            now = self.clock.now()
            if now >= seconds:
                break
            if now >= len(queue) * seconds / 8:
                queue.append([now, len(eng.queue)])
            if tracer is not None and not traced:
                if not tracing_on and now >= t_on:
                    tracer.start()
                    tracing_on = True
                    traced_at = {"prefills": eng.prefills,
                                 "decodes": eng.decode_steps}
                elif tracing_on and now >= t_on + t_len:
                    tracer.stop()
                    tracing_on, traced = False, True
                    traced_at = {"prefills": eng.prefills - traced_at["prefills"],
                                 "decodes": eng.decode_steps - traced_at["decodes"]}
            while pending and pending[0]["due"] <= now:
                r = pending.popleft()
                late.append(now - r["due"])
                eng.submit(Request(rid=r["rid"], prompt=r["prompt"],
                                   max_new_tokens=r["max_new"],
                                   arrival=r["due"], feature_ids=r["features"]))
            if eng.queue or eng.slots:
                if tracing_on:
                    with tracing.span("step"):
                        eng.step()
                else:
                    eng.step()
                seen = self._stamp(first, seen)
            else:
                wait = (pending[0]["due"] if pending else seconds) - now
                if tracing_on:
                    with tracing.span("idle"):
                        time.sleep(max(0.0, min(wait, seconds - now)))
                else:
                    time.sleep(max(0.0, min(wait, seconds - now)))
        window_s = self.clock.now()
        queue.append([window_s, len(eng.queue)])
        if tracing_on:
            tracer.stop()
            traced = True
            traced_at = {"prefills": eng.prefills - traced_at["prefills"],
                         "decodes": eng.decode_steps - traced_at["decodes"]}
        tokens = eng.generated_tokens - tokens0
        window_compiles = spec.counter.programs - compiles
        submitted = len(self.requests) - len(pending)
        memory = harness.device_info(spec.devices, spec.cell.chips)
        # drain: every request due in the window gets its first token
        drain_end = self.clock.now() + DRAIN_S
        while (len(first) < submitted and (eng.queue or eng.slots)
               and self.clock.now() < drain_end):
            eng.step()
            seen = self._stamp(first, seen)
        due = {r["rid"]: r["due"] for r in self.requests[:submitted]}
        ttft: List[Optional[float]] = [
            first[rid] - d if rid in first else None for rid, d in due.items()]
        while eng.queue or eng.slots:  # finish the rest for the check
            if self.clock.now() >= drain_end:
                break
            eng.step()
        counts = {
            "window_s": window_s, "tokens": tokens, "ttft_s": ttft,
            "submitted": submitted, "queue": queue,
            "traced_prefills": traced_at.get("prefills", 0) if traced else 0,
            "traced_decodes": traced_at.get("decodes", 0) if traced else 0,
        }
        late_sorted = sorted(late) or [0.0]
        return {
            "setup_s": setup_s, "counts": counts, "memory": memory,
            "window_compiles": window_compiles,
            "trace": tracer.reduce() if traced else None,
            "late_ms": {"median": 1e3 * late_sorted[len(late_sorted) // 2],
                        "max": 1e3 * late_sorted[-1]},
            "failed": sum(x is None for x in ttft),
        }

    def finished(self) -> List[Dict[str, Any]]:
        prompts = {r["rid"]: r["prompt"] for r in self.requests}
        return [{"rid": c.rid, "prompt": prompts[c.rid],
                 "tokens": np.asarray(c.tokens, np.int32)}
                for c in self.engine.completions]

    def close(self) -> None:
        self.store.close()
        self.engine = None
        self.fcache = None
        gc.collect()


def sample(finished: List[Dict[str, Any]], seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rest = order[1:]
    g = data.rng(seed, 6)
    pick = [order[0]] + [rest[i] for i in g.choice(
        len(rest), size=min(k - 1, len(rest)), replace=False)]
    return [finished[i] for i in pick]


def checks(spec, ev, control=None) -> List[harness.Check]:
    """The widest gap by which a served token's logit lies below the
    reference's best at its position, over the sampled requests.  With
    ``control``, the gap of the token that ``control`` ranks first."""
    c, t = spec.cell.config, spec.cell.traffic
    picked = ev["picked"]
    capacity = t["prompt"]["max"] + t["output"]["max"]
    params = weights.make_params(ref.layout(c), c, spec.seed)
    gaps = ref.served_gaps(params, [(p["prompt"], p["tokens"]) for p in picked],
                           c, capacity, control=control)
    del params
    tokens = sum(len(p["tokens"]) for p in picked)
    print(f"[check] {len(picked)} requests, {tokens} served tokens compared",
          flush=True)
    return [
        harness.Check("served_logit_gap", max(gaps) if gaps else math.inf,
                      t["limits"]["served_logit_gap"]),
        harness.Check("tokens_unchecked",
                      float(max(0, t["check_tokens"] - tokens)), 0.0),
    ]


def control(spec, ev) -> Dict[str, List[harness.Check]]:
    """Readings of the control: the reference in float8, the precision
    below the configuration's bfloat16, ranking the tokens."""
    return {"control_fp8": checks(spec, ev, control=ref.FP8)}


def evidence(spec: harness.RunSpec) -> Dict[str, Any]:
    s = Session(spec)
    w = s.window()
    ev = {"window": w, "picked": sample(
        s.finished(), spec.seed, spec.cell.traffic["check_requests"])}
    s.close()
    return ev


def run(spec: harness.RunSpec) -> harness.Window:
    ev = evidence(spec)
    w = ev["window"]
    found = checks(spec, ev)
    c = w["counts"]
    return harness.Window(
        setup_s=w["setup_s"], attempted=c["submitted"], failed=w["failed"],
        counts=c, checks=found, trace=w["trace"], memory=w["memory"],
        notes={"window_compiles": w["window_compiles"],
               "generator_late_ms": w["late_ms"]},
    )
