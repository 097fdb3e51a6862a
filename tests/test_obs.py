"""Observability layer: trace recorder, metrics registry, drift detector.

Covers the ISSUE-8 satellite list: recorder + registry thread-safety
under concurrent producers, ring wraparound, disabled-mode
zero-allocation, Chrome trace-event schema validity, drift tolerance
units, and the ``IOStats.snapshot()`` torn-read fix.  The five-layer
trace acceptance run lives at the bottom: the in-process 2-host cluster
driven through an ``InputPipeline`` produces spans from storage, cache,
remote, and pipeline; the full launcher (train spans included) is the
slow-marked variant.
"""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.obs import drift, metrics, trace
from repro.obs.metrics import (
    HIST_BOUNDS_S,
    HIST_BUCKETS,
    Histogram,
    MetricsRegistry,
    delta,
    to_prometheus,
)
from repro.storage.record_store import IOStats, RecordStore, write_records


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.disable()
    yield
    trace.disable()


# ------------------------------------------------------------- tracing
def test_span_records_complete_event():
    rec = trace.enable(capacity_per_thread=64)
    with trace.span("t/a", "cat1", args={"k": 1}):
        pass
    trace.instant("t/b", "cat1")
    trace.disable()
    evs = rec.drain()
    assert [e["name"] for e in evs] == ["t/a", "t/b"]
    x, i = evs
    assert x["ph"] == "X" and x["dur"] >= 0 and x["args"] == {"k": 1}
    assert i["ph"] == "i" and i["s"] == "t"
    assert x["ts"] <= i["ts"]


def test_disabled_mode_is_noop_singleton():
    assert not trace.enabled()
    s1 = trace.span("x", "y")
    s2 = trace.span("z")
    assert s1 is s2  # shared singleton: zero allocation per call
    with s1:
        pass
    assert s1.duration_s == 0.0
    assert trace.instant("x") is None


def test_timed_measures_in_both_modes():
    assert not trace.enabled()
    with trace.timed("w") as sp:
        x = sum(range(1000))
    assert x and sp.duration_s > 0.0
    rec = trace.enable(capacity_per_thread=64)
    with trace.timed("w") as sp:
        pass
    trace.disable()
    assert sp.duration_s >= 0.0
    assert [e["name"] for e in rec.drain()] == ["w"]


def test_timed_reuses_pooled_spans():
    """Steady state allocates nothing: the span returned to the pool on
    exit is the one handed out next."""
    assert not trace.enabled()
    with trace.timed("a") as sp1:
        pass
    with trace.timed("b") as sp2:
        pass
    assert sp1 is sp2


def test_ring_wraparound_keeps_newest():
    rec = trace.enable(capacity_per_thread=8)
    for k in range(20):
        trace.instant(f"e{k}")
    trace.disable()
    evs = rec.drain()
    assert [e["name"] for e in evs] == [f"e{k}" for k in range(12, 20)]
    assert rec.dropped == 12
    assert rec.to_chrome()["otherData"]["dropped_events"] == 12


def test_resume_keeps_recorder_and_rings():
    rec = trace.enable(capacity_per_thread=64)
    trace.instant("before")
    trace.disable()
    assert trace.resume() is rec
    trace.instant("after")
    trace.disable()
    assert [e["name"] for e in rec.drain()] == ["before", "after"]


def test_trace_thread_safety_and_chrome_schema(tmp_path):
    """Concurrent producers each get their own ring; the exported doc is
    valid Chrome trace JSON with per-thread lanes and every event."""
    rec = trace.enable(capacity_per_thread=4096)
    n_threads, per_thread = 8, 500
    # all workers alive at once, else the OS reuses thread idents and
    # lanes legitimately merge
    barrier = threading.Barrier(n_threads)

    def worker(t):
        barrier.wait()
        for k in range(per_thread):
            if k % 3 == 2:
                trace.instant(f"w{t}/i", "load")
            else:
                with trace.span(f"w{t}/s", "load", args={"k": k}):
                    pass

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    trace.disable()

    path = tmp_path / "trace.json"
    doc = rec.export_chrome(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    evs = [e for e in loaded["traceEvents"] if e["ph"] in ("X", "i")]
    assert len(evs) == n_threads * per_thread
    assert rec.dropped == 0
    tids = {e["tid"] for e in evs}
    assert len(tids) == n_threads  # one lane per producer thread
    meta = [e for e in loaded["traceEvents"] if e["ph"] == "M"]
    assert {m["tid"] for m in meta} >= tids
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)  # drain() sorts across rings
    for e in evs:
        assert isinstance(e["name"], str) and isinstance(e["pid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        else:
            assert e["s"] == "t"
    assert doc["traceEvents"][-1] == loaded["traceEvents"][-1]


# ------------------------------------------ the mirror on the profiler
def _innermost_parents(events):
    """name → the name of the shortest other event containing it (same
    lane), from ``(lane, name, start, end, args)`` tuples."""
    out = {}
    for lane, name, s, e, _ in events:
        around = [(e2 - s2, n2) for l2, n2, s2, e2, _ in events
                  if l2 == lane and n2 != name and s2 <= s and e <= e2]
        out[name] = min(around)[1] if around else None
    return out


def _xplane_host_events(log_dir, names):
    import glob
    import os

    from jax.profiler import ProfileData

    [path] = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in names:
                    out.append((ln.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_recorded_spans_mirror_onto_the_profiler_trace(tmp_path):
    """Recording on, spans and timed spans come out in the xplane host
    plane with their names, their args and the ring's nesting; recording
    off, nothing is mirrored, though the profiler is taking a trace."""
    jax = pytest.importorskip("jax")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span("serve/emit", "serve"):
            pass
        with trace.timed("pipeline/wait", "pipeline"):
            pass
        rec = trace.enable(capacity_per_thread=64)
        with trace.span("serve/admit", "serve", args={"rid": 7}):
            with trace.span("serve/prefill", "serve"):
                jax.numpy.ones(4).block_until_ready()
            with trace.timed("pipeline/fetch", "pipeline"):
                pass
        trace.disable()
    finally:
        jax.profiler.stop_trace()
    names = {"serve/admit", "serve/prefill", "pipeline/fetch",
             "serve/emit", "pipeline/wait"}
    mirrored = _xplane_host_events(str(tmp_path), names)
    ring = [(e["tid"], e["name"], e["ts"], e["ts"] + e["dur"],
             e.get("args", {})) for e in rec.drain()]
    assert sorted(m[1] for m in mirrored) == sorted(r[1] for r in ring) == [
        "pipeline/fetch", "serve/admit", "serve/prefill"]
    assert {m[1]: m[4] for m in mirrored}["serve/admit"] == {"rid": 7}
    assert {r[1]: r[4] for r in ring}["serve/admit"] == {"rid": 7}
    nesting = {"serve/admit": None, "serve/prefill": "serve/admit",
               "pipeline/fetch": "serve/admit"}
    assert _innermost_parents(mirrored) == _innermost_parents(ring) == nesting


def test_obs_imports_jax_only_when_recording_starts():
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    code = ("import sys; from repro.obs import trace; "
            "print('jax' in sys.modules); trace.enable(); "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.stdout.split() == ["False", "True"]


def test_spans_added_to_hot_paths_cost_nothing_with_tracing_off(monkeypatch):
    """With tracing off, one engine step, one ``Trainer`` step and one
    ``margins_csr`` call push no ring event and acquire no span of their
    own; the pipeline's timed spans measure as they always do."""
    jax = pytest.importorskip("jax")
    from repro.configs.granite_3_8b import smoke_config
    from repro.models import model as model_lib
    from repro.serve import Request, ServeEngine
    from repro.svm.dcd import DCDSolver
    from repro.svm.sparse import CSRBatch
    from repro.train.loop import Trainer, TrainLoopConfig, make_shuffler

    rec = trace.enable(capacity_per_thread=64)
    trace.disable()
    acquired = []
    real_acquire = trace._acquire

    def counting(name, *a):
        acquired.append(name)
        return real_acquire(name, *a)

    monkeypatch.setattr(trace, "_acquire", counting)
    cfg = smoke_config()

    eng = ServeEngine(cfg, model_lib.init_params(cfg, jax.random.PRNGKey(0)),
                      max_batch=2, prompt_capacity=8, max_new_tokens=4)
    eng.submit(Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=3))
    eng.step()
    assert eng.prefills == 1 and eng.decode_steps == 1
    assert acquired == []

    rows = np.random.default_rng(0).integers(0, 64, (8, 17)).astype(np.int32)
    trainer = Trainer(
        cfg.replace(vocab_size=64),
        lambda idx: {"tokens": rows[idx, :-1], "labels": rows[idx, 1:]},
        make_shuffler("lirs", 8, 2, seed=0),
        TrainLoopConfig(epochs=1, max_steps=1),
    )
    assert trainer.train()["steps"] == 1
    assert acquired and {n.split("/")[0] for n in acquired} == {"pipeline"}
    assert trainer.pipeline.stats.t_load > 0
    assert trainer.pipeline.stats.t_wait > 0
    del acquired[:]

    solver = DCDSolver(32, 2)
    solver.w = np.arange(32, dtype=np.float64)
    m = solver.margins_csr(CSRBatch(
        indices=np.array([1, 3, 2], np.int32),
        values=np.array([1.0, 2.0, 0.5], np.float32),
        row_ptr=np.array([0, 2, 3], np.int32),
        labels=np.ones(2, np.float32)))
    np.testing.assert_allclose(m, [7.0, 1.0])
    assert acquired == []
    assert rec.drain() == []


def test_margins_csr_records_the_upload_of_an_unchanged_w_once():
    """Ring on, two ``margins_csr`` calls with one ``w``: two
    ``svm/put`` spans, and ``svm/put_w`` inside the first alone."""
    pytest.importorskip("jax")
    from repro.svm.dcd import DCDSolver
    from repro.svm.sparse import CSRBatch

    solver = DCDSolver(32, 2)
    solver.w = np.arange(32, dtype=np.float64)
    csr = CSRBatch(indices=np.array([1, 3, 2], np.int32),
                   values=np.array([1.0, 2.0, 0.5], np.float32),
                   row_ptr=np.array([0, 2, 3], np.int32),
                   labels=np.ones(2, np.float32))
    rec = trace.enable(capacity_per_thread=64)
    for _ in range(2):
        np.testing.assert_allclose(solver.margins_csr(csr), [7.0, 1.0])
    trace.disable()
    events = sorted(rec.drain(), key=lambda e: e["ts"])
    puts = [e for e in events if e["name"] == "svm/put"]
    put_w = [e for e in events if e["name"] == "svm/put_w"]
    assert len(puts) == 2 and len(put_w) == 1
    inner, first = put_w[0], puts[0]
    assert first["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= first["ts"] + first["dur"]
    assert solver.w_uploads == 1 and solver.margins_calls == 2


# ------------------------------------------------------------- metrics
def test_histogram_bucket_units():
    """Bucket k's upper bound is 1 µs · 2^k — the drift between an
    observation and its bucket bound is at most one octave."""
    h = Histogram("t")
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(1e-6) == 0
    assert h.bucket_index(1.9e-6) == 1
    assert h.bucket_index(3.9e-6) == 2
    assert h.bucket_index(1.0) == 20  # 1 s ≈ 2^20 µs
    assert h.bucket_index(1e9) == HIST_BUCKETS - 1
    for k, bound in enumerate(HIST_BOUNDS_S):
        assert bound == pytest.approx(1e-6 * 2**k)
        assert h.bucket_index(bound) == k
    h.observe(5e-6)
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["sum"] == pytest.approx(5e-6)
    assert snap["buckets"][h.bucket_index(5e-6)] == 1
    assert h.quantile(0.5) == HIST_BOUNDS_S[h.bucket_index(5e-6)]


def test_registry_thread_safety_under_concurrent_producers():
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 2000

    def worker():
        for k in range(per_thread):
            reg.counter("c").inc()
            reg.histogram("h").observe(k * 1e-6)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = reg.snapshot()
    assert snap["counters"]["c"] == n_threads * per_thread
    assert snap["histograms"]["h"]["count"] == n_threads * per_thread
    assert sum(snap["histograms"]["h"]["buckets"]) == n_threads * per_thread


def test_snapshot_delta_and_prometheus_roundtrip():
    reg = MetricsRegistry()
    reg.counter("reads").inc(10)
    reg.gauge("depth").set(3)
    reg.histogram("lat").observe(2e-6)
    a = reg.snapshot()
    reg.counter("reads").inc(5)
    reg.gauge("depth").set(7)
    reg.histogram("lat").observe(2e-6)
    b = reg.snapshot()
    d = delta(b, a)
    assert d["counters"]["reads"] == 5
    assert d["gauges"]["depth"] == 7  # gauges take the newer value
    assert d["histograms"]["lat"]["count"] == 1
    json.dumps(b)  # snapshots are plain JSON

    text = to_prometheus(b)
    assert "# TYPE reads counter" in text
    assert "reads 15" in text
    assert "# TYPE depth gauge" in text
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert "lat_count 2" in text
    # cumulative buckets: every le line monotonically non-decreasing
    counts = [
        int(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith("lat_bucket")
    ]
    assert counts == sorted(counts)


def test_collectors_absorb_structs_without_moving_increments(tmp_path):
    path = str(tmp_path / "d.rrec")
    write_records(path, [b"x" * 64 for _ in range(16)], record_size=64)
    store = RecordStore(path)
    reg = MetricsRegistry()
    metrics.bind_store(reg, store)
    store.read_batch_into(np.arange(8))
    snap = reg.snapshot()
    assert snap["counters"]["storage/batch_records"] == 8
    store.close()


def test_default_registry_observe_and_reset():
    reg = metrics.reset_registry()
    metrics.observe("x/lat", 3e-6)
    assert reg.snapshot()["histograms"]["x/lat"]["count"] == 1
    reg2 = metrics.reset_registry()
    assert reg2 is metrics.get_registry() and reg2 is not reg
    metrics.observe("x/lat", 3e-6)  # lands in the new registry
    assert reg2.snapshot()["histograms"]["x/lat"]["count"] == 1


# ------------------------------------------------------------- IOStats
def test_iostats_snapshot_is_atomic_under_writers():
    """The torn-read fix: snapshot() must never see half an account()
    call.  account_batch bumps batch_records and batch_ios under one
    lock, so their K:1 ratio must hold in every snapshot."""
    st = IOStats()
    STOP = threading.Event()
    K = 4  # records per (single-extent) io in this synthetic workload
    offs = np.array([0], dtype=np.int64)
    lens = np.array([K * 64], dtype=np.int64)
    recs = np.array([K], dtype=np.int64)

    def writer():
        while not STOP.is_set():
            st.account_batch(offs, lens, recs)

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for th in threads:
        th.start()
    try:
        for _ in range(2000):
            s = st.snapshot()
            assert s["batch_records"] == K * s["batch_ios"], s
    finally:
        STOP.set()
        for th in threads:
            th.join()


def test_iostats_delta_excludes_position():
    a = {"batch_records": 10, "last_offset": 100}
    b = {"batch_records": 25, "last_offset": 40}
    d = IOStats.delta(b, a)
    assert d["batch_records"] == 15
    assert d["last_offset"] == 40  # a position, not a rate


# --------------------------------------------------------------- drift
def test_drift_tolerance_units():
    """Tolerances are in the metric's own unit: absolute fractions for
    rates/splits, fraction-of-n records for reads, relative for time."""
    r = drift.DriftReport()
    c = r.add("hit_rate", 0.95, 0.96, tol_abs=0.02)
    assert c.ok and c.slack == 0.02 and c.error == pytest.approx(-0.01)
    c = r.add("reads", 530.0, 500.0, tol_abs=0.05 * 1024)
    assert c.ok and c.slack == pytest.approx(51.2)
    c = r.add("t_read", 1.25, 1.0, tol_rel=0.10)
    assert not c.ok and c.slack == pytest.approx(0.10)  # 10% of expected
    assert not r.ok and [f.name for f in r.failed] == ["t_read"]
    with pytest.raises(AssertionError, match="t_read"):
        r.assert_ok()
    assert drift.hit_rate_tolerance("belady") == 0.02
    assert drift.hit_rate_tolerance("lru") == 0.05


def test_drift_single_host_report_belady_exact():
    """Belady at capacity c serves exactly c·n from DRAM: measured
    counts equal to the closed form must be in tolerance, counts off by
    more than the slack must fail."""
    n, c = 1024, 0.5
    good = drift.single_host_report(
        n_records=n, record_bytes=4096, capacity_frac=c, policy="belady",
        planner_on=True, window_frac=0.1, batch_frac=1 / 32, epochs=2,
        storage_records=2 * (1 - c) * n,
    )
    assert good.ok, good.format()
    bad = drift.single_host_report(
        n_records=n, record_bytes=4096, capacity_frac=c, policy="belady",
        planner_on=True, window_frac=0.1, batch_frac=1 / 32, epochs=2,
        storage_records=2 * ((1 - c) * n + 0.10 * n),  # 10% of n over floor
    )
    assert not bad.ok
    assert "storage_records_per_epoch" in [f.name for f in bad.failed]


def test_drift_single_host_report_prices_time_through_device():
    n, c = 1024, 0.25
    per_epoch = (1 - c) * n
    rep = drift.single_host_report(
        n_records=n, record_bytes=4096, capacity_frac=c, policy="belady",
        planner_on=True, window_frac=0.1, batch_frac=1 / 32, epochs=1,
        storage_records=per_epoch, storage_ios=per_epoch / 4,
        storage_bytes=per_epoch * 4096, device="optane",
    )
    names = [ck.name for ck in rep.checks]
    assert "t_epoch_read_s" in names
    assert rep.ok, rep.format()


def test_drift_distributed_report_uses_direct_local_count():
    """The local split comes straight from the source-counted local
    tier (``aggregate_io()``'s ``cache_hits − peer_refills −
    prefetch_fills``) — no ``total − remote − storage`` derivation."""
    n, hosts, c = 1024, 2, 0.8
    from repro.storage.devices import distributed_hit_model

    split = distributed_hit_model(c, hosts, "belady")
    rep = drift.distributed_report(
        n_records=n, hosts=hosts, capacity_frac_global=c, policy="belady",
        window_frac=0.1, epochs=2,
        remote_hits=2 * split["remote"] * n,
        storage_records=2 * split["storage"] * n,
        local_hits=2 * split["local"] * n,
    )
    assert rep.ok, rep.format()
    local = next(c for c in rep.checks if c.name == "split/local")
    assert local.measured == pytest.approx(split["local"], abs=1e-9)
    with pytest.raises(TypeError):
        drift.distributed_report(
            n_records=n, hosts=hosts, capacity_frac_global=c,
            policy="belady", window_frac=0.1, epochs=2,
            remote_hits=0.0, storage_records=0.0,
        )


# -------------------------------------------- five-layer trace (fast)
def test_cluster_pipeline_trace_covers_io_layers(tmp_path):
    """A 2-host Belady cluster driven through an InputPipeline records
    spans from storage, cache, remote, and pipeline in one trace (the
    launcher's slow test below adds the train layer)."""
    from repro.core.pipeline import InputPipeline
    from repro.core.shuffler import LIRSShuffler
    from repro.prefetch.distributed import ClusterFetcher, make_cluster

    n, batch, rs = 256, 32, 64
    path = str(tmp_path / "d.rrec")
    write_records(
        path, [bytes([k % 256]) * rs for k in range(n)], record_size=rs
    )
    sh = LIRSShuffler(n, batch, seed=3)
    rec = trace.enable()
    cl = make_cluster(
        lambda: RecordStore(path), sh, 2,
        budget_bytes=n * rs // 2, lookahead=4, max_epochs=2,
        policy="belady",
    )
    fetcher = ClusterFetcher(cl)
    pipe = InputPipeline(
        batch_iter_fn=fetcher.batch_iter, fetch_fn=fetcher, prefetch=2
    )
    for epoch in range(2):
        for _ in pipe.epoch(epoch):
            pass
    fetcher.close()
    trace.disable()
    cats = {e["cat"] for e in rec.drain() if e["ph"] in ("X", "i")}
    assert {"storage", "cache", "remote", "pipeline"} <= cats
    json.loads(json.dumps(rec.to_chrome()))  # exportable


@pytest.mark.slow
def test_launcher_two_host_trace_covers_all_five_layers(tmp_path):
    """ISSUE-8 acceptance: a 2-host Belady launcher run with tracing on
    yields a Perfetto-loadable trace containing spans from every layer,
    and its drift report is within tolerance."""
    from repro.launch.train import main as train_main

    tpath = str(tmp_path / "trace.json")
    # 512 records against 0.06 MB/host keeps the cluster capacity-
    # constrained: with slack capacity consumers *retain* peer-fetched
    # records (replication) and the uniform-holder split model the drift
    # detector prices no longer applies
    summary = train_main([
        "--smoke", "--num-records", "512", "--seq-len", "32",
        "--batch", "16", "--epochs", "3", "--cache-mb", "0.06",
        "--hosts", "2", "--eviction-policy", "belady",
        "--trace", tpath,
        "--metrics-json", str(tmp_path / "metrics.json"),
    ])
    doc = json.loads((tmp_path / "trace.json").read_text())
    cats = {e.get("cat") for e in doc["traceEvents"] if e["ph"] in ("X", "i")}
    assert {"storage", "cache", "remote", "pipeline", "train"} <= cats
    assert summary["drift"]["ok"], summary["drift"]
    snap = json.loads((tmp_path / "metrics.json").read_text())
    assert snap["counters"]["cluster/storage_records"] > 0
    assert snap["histograms"]["remote/peer_rtt_seconds"]["count"] > 0
