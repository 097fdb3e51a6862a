"""SVM scoring window: shuffled kdd-shaped records through the LIRS
shuffler, the Belady DRAM tier and the ``InputPipeline``, packed into
CSR batches on the producer thread, scored by ``DCDSolver.margins_csr``.

Set-up writes the record file from the seed, indexes it with the
program's ``LocationGenerator``, runs one warm epoch through the data
plane (so the window sees the tier's steady state) and one
``margins_csr`` call (its compile).  The window then scores batches from
the next epoch on, and closes after the batch whose margins are on the
host when the deadline has passed.  Every batch of the window is kept
and checked once the window has closed: its CSR arrays against the
records as generated, its margins against float64 margins on the host.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.chip import data, harness, tracing
from benchmarks.chip.reference import svm as ref


class Feed:
    """The pipeline's fetch: the data plane's own read, then
    ``pack_csr_batch``, with each batch's record ids kept."""

    def __init__(self, plane, dim: int, trace: bool):
        from repro.svm.sparse import pack_csr_batch

        self.plane, self.dim, self.trace = plane, dim, trace
        self.pack = pack_csr_batch

    def fetch(self, idx):
        if self.trace:
            with tracing.span("fetch"):
                return np.array(idx), self.pack(self.plane(idx), self.dim)
        return np.array(idx), self.pack(self.plane(idx), self.dim)


class Session:
    """One data plane and solver from set-up to the end of the window."""

    def __init__(self, spec: harness.RunSpec):
        from repro.core.location import LocationGenerator
        from repro.core.pipeline import InputPipeline
        from repro.core.readpath import ReadPathConfig, build_data_plane
        from repro.storage.record_store import RecordStore
        from repro.svm.dcd import DCDSolver
        from repro.train import loop

        self.spec = spec
        c, t = spec.cell.config, spec.cell.traffic
        self.c, self.t = c, t
        n, dim, batch = c["num_records"], c["num_features"], t["batch"]
        self.recs = data.sparse_records(spec.seed, n, dim,
                                        (c["nnz_min"], c["nnz_max"]))
        path = str(spec.work_dir / "kdd.rrec")
        self.avg_record_bytes = data.write_sparse(path, self.recs)
        self.store = RecordStore(path)
        LocationGenerator().generate(self.store)
        payload = int(self.store.lengths().sum())
        self.shuffler = loop.make_shuffler(t["shuffler"], n, batch,
                                           seed=spec.seed)
        self.plane = build_data_plane(self.store, ReadPathConfig(
            mode=t["mode"], shuffler=self.shuffler,
            cache_budget_bytes=int(t["tier_fraction"] * payload),
            lookahead=t["lookahead"], eviction_policy=t["eviction_policy"],
            workers=t["io_workers"],
        ))
        self.feed = Feed(self.plane, dim, spec.trace)
        self.pipeline = InputPipeline(batch_iter_fn=self.plane.batch_iter,
                                      fetch_fn=self.feed.fetch)
        self.solver = DCDSolver(dim, n)
        self.solver.w = ref.weights(spec.seed, dim)
        self.k = 0
        for e in range(t["warm_epochs"]):  # the tier's steady state
            for ids, csr in self.pipeline.epoch(e):
                if not self.k:
                    self.solver.margins_csr(csr)  # compiles csr_dot
                    self.k = ref.pad_width(csr)
        self.epoch = t["warm_epochs"]

    def window(self) -> Dict[str, Any]:
        spec = self.spec
        st, io = self.pipeline.stats, self.store.stats
        before = {"t_wait": st.t_wait, "t_load": st.t_load,
                  "bytes": io.bytes_read}
        tracer = tracing.Tracer(spec.work_dir / "trace") if spec.trace else None
        t_on, t_len = tracing.trace_plan(spec.seconds)
        kept: List[tuple] = []
        margins_s = 0.0
        compiles = spec.counter.programs
        setup_s = time.perf_counter() - spec.t_start
        t0 = time.perf_counter()
        deadline = t0 + spec.seconds
        tracing_on = traced = False
        done = False
        while not done:
            it = self.pipeline.epoch(self.epoch)
            self.epoch += 1
            for ids, csr in it:
                now = time.perf_counter()
                if tracer is not None and not traced:
                    if not tracing_on and now - t0 >= t_on:
                        tracer.start()
                        tracing_on = True
                    elif tracing_on and now - t0 >= t_on + t_len:
                        tracer.stop()
                        tracing_on, traced = False, True
                if tracing_on:
                    with tracing.span("margins"):
                        m = self.solver.margins_csr(csr)
                else:
                    m = self.solver.margins_csr(csr)
                margins_s += time.perf_counter() - now
                kept.append((ids, csr, m))
                if time.perf_counter() >= deadline:
                    done = True
                    break
            if done:
                t1 = time.perf_counter()
                it.close()  # abandon the epoch: the producer is joined
        if tracing_on:
            tracer.stop()
            traced = True
        records = sum(len(ids) for ids, _, _ in kept)
        counts = {
            "window_s": t1 - t0,
            "batches": len(kept),
            "records": records,
            "load_s": st.t_load - before["t_load"],
            "input_wait_s": st.t_wait - before["t_wait"],
            "storage_bytes": io.bytes_read - before["bytes"],
            "margins_s": margins_s,
            "batch": self.t["batch"],
            "k": self.k,
            "peaks": spec.peaks,
        }
        return {
            "setup_s": setup_s, "counts": counts, "kept": kept,
            "window_compiles": spec.counter.programs - compiles,
            "memory": harness.device_info(spec.devices, spec.cell.chips),
            "trace": tracer.reduce() if traced else None,
        }

    def close(self) -> None:
        self.plane.close()
        self.store.close()
        self.solver = None
        gc.collect()


def checks(spec, ev, margins_of=None) -> List[harness.Check]:
    """Every batch of the window: its CSR arrays against the records as
    generated, its margins against float64 margins over the float64
    weights, as a share of each row's sum of |terms|.  ``margins_of``
    puts another computation of the margins in the program's place."""
    lim = spec.cell.traffic["limits"]
    mismatched, worst = 0, 0.0
    for ids, csr, m in ev["kept"]:
        want = ref.csr_of(ev["recs"], ids)
        if not ref.same_csr(csr, want):
            mismatched += 1
        if margins_of is not None:
            m = margins_of(want, ev["w"])
        worst = max(worst, ref.margin_error(m, want, ev["w"]))
    return [
        harness.Check("csr_mismatch", float(mismatched), lim["csr_mismatch"]),
        harness.Check("margin_error", worst, lim["margin_error"]),
    ]


def control(spec, ev) -> Dict[str, List[harness.Check]]:
    """Readings of the control: the margins in bfloat16, the precision
    below the configuration's float32, in the program's place."""
    return {"control_bf16": checks(spec, ev, ref.control_margins)}


def evidence(spec: harness.RunSpec) -> Dict[str, Any]:
    s = Session(spec)
    w = s.window()
    ev = {"window": w, "recs": s.recs, "w": s.solver.w, "kept": w.pop("kept"),
          "avg_record_bytes": s.avg_record_bytes}
    s.close()
    return ev


def run(spec: harness.RunSpec) -> harness.Window:
    ev = evidence(spec)
    w = ev["window"]
    found = checks(spec, ev)
    c = w["counts"]
    return harness.Window(
        setup_s=w["setup_s"], attempted=c["records"], failed=0, counts=c,
        checks=found, trace=w["trace"], memory=w["memory"],
        notes={"window_compiles": w["window_compiles"],
               "avg_record_bytes": ev["avg_record_bytes"]},
    )
