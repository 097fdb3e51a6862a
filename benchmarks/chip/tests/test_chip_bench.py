"""CPU tests of the chip benchmark: the trace reduction, the window
arithmetic, the operation counts, the refusals, the lookup by name, and
each cell driven end to end at a tiny size, sound and with its timed
path broken.

    python -m pytest benchmarks/chip/tests -p xdist -n 6
"""
from __future__ import annotations

import json
import math
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import flops, harness, peaks, run, stats, trace_reduce
from benchmarks.chip.tests import tiny

HERE = Path(__file__).resolve().parent
BENCH_DIR = HERE.parent
ROOT = BENCH_DIR.parents[1]
RECORDED = HERE / "data" / "recorded_svm.json"
CELLS = ["granite-3-8b.train-4k-lirs", "svm-kdd2010.score-lirs-tier25",
         "granite-3-8b.serve-chat-0.8knee"]


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU is no chip the benchmark measures; the tests give it
    peaks so a run can be driven through."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})


@pytest.fixture
def small(tmp_path, monkeypatch, cpu_peaks):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    bench, bench_dir = tiny.make(tmp_path)

    def go(cell, **kw):
        import jax

        return run.run(tiny.args(cell, **kw), devices=jax.devices(),
                       bench=bench, bench_dir=bench_dir, work=tmp_path)

    return go


# ----------------------------------------------------------- trace reduction


def test_union_merges_overlaps_and_gaps_fill_the_rest():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    busy = trace_reduce.union([(1, 2), (4, 6)])
    assert trace_reduce.gaps(busy, 0, 10) == [(0, 1), (2, 4), (6, 10)]
    assert trace_reduce.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def _trace():
    """Two devices' modules and ops over a traced span of 100 ns, and the
    host spans the gaps fall in."""
    span = [trace_reduce.TRACED_SPAN, 1000, 100]
    dev0 = ["/device:TPU:0",
            [["jit_step(11)", 1000, 30], ["jit_step(11)", 1050, 30],
             ["jit__lambda(7)", 1090, 5]],
            [["%fusion.1 = f32[8] fusion(...)", 1000, 20],
             ["%fusion.2 = f32[8] fusion(...)", 1010, 20],   # overlaps
             ["%fusion.1 = f32[8] fusion(...)", 1050, 30],
             ["%copy.3 = f32[8] copy(...)", 1090, 5],
             ["%late = f32[8] copy(...)", 1200, 5]]]         # outside
    dev1 = ["/device:TPU:1", [["jit_step(11)", 1000, 100]],
            [["%fusion.1 = f32[8] fusion(...)", 1000, 100]]]
    host = [span, ["bench/fetch", 1030, 20], ["bench/margins", 1080, 20],
            ["bench/step", 1095, 10]]
    return {"device": [dev0, dev1], "host": host}


def test_reduce_busy_idle_programs_and_gap_attribution():
    r = trace_reduce.reduce_trace(_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0 busy 30 + 30 + 5 of 100 ns, device 1 all 100: mean 82.5
    assert r["busy_s"] == pytest.approx(82.5e-9)
    assert trace_reduce.idle_share(r) == pytest.approx(17.5)
    step = r["programs"]["jit_step"]
    assert step["calls"] == 3 and step["device_s"] == pytest.approx(160e-9)
    assert r["programs"]["jit__lambda"]["by_key"] == {"7": [1, 5e-9]}
    gaps = dict(r["idle_gaps"])
    # device 0 idles 1030-1050 (fetch), 1080-1090 (margins), 1095-1100
    # (margins and step overlap it alike: the shorter span wins); the
    # mean over the two devices halves each
    assert gaps["bench/fetch"] == pytest.approx(10e-9)
    assert gaps["bench/margins"] == pytest.approx(5e-9)
    assert gaps["bench/step"] == pytest.approx(2.5e-9)
    ops = dict(r["device_ops"])
    assert ops["jit_step/fusion.1"] == pytest.approx((20 + 30 + 100) / 2 * 1e-9)
    assert "jit_step/late" not in ops


def test_reduce_refuses_a_trace_without_its_span_or_device():
    t = _trace()
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"device": t["device"], "host": []})
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"device": [], "host": t["host"]})


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_reduce_a_recorded_chip_trace():
    """A slice of a traced svm window recorded on a TPU v5 lite: the
    margins program shows, its busy time is the union of its ops, and
    every idle gap goes to a host span or to none."""
    flat = json.loads(RECORDED.read_text())
    r = trace_reduce.reduce_trace(flat)
    assert 0 < r["busy_s"] < r["window_s"]
    assert "jit_csr_dot" in r["programs"]
    ops = [iv for _, _, o in flat["device"] for iv in o]
    lo = flat["host"][0][1]
    hi = lo + flat["host"][0][2]
    busy = sum(e - s for s, e in trace_reduce.union(trace_reduce.clip(
        [(float(s), float(s) + float(d)) for _, s, d in ops], lo, hi)))
    assert r["busy_s"] == pytest.approx(busy / len(flat["device"]) / 1e9)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(k.startswith("bench/") or k == "(no host span)"
               for k, _ in r["idle_gaps"])


# ----------------------------------------------------------- window arithmetic


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(300, 10.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_tail_is_over_every_request_and_failures_miss():
    lat = [0.01 * i for i in range(1, 101)]
    assert stats.tail(lat, 95) == pytest.approx(0.95)
    # six requests that never got a first token push p95 past every
    # measured latency
    assert stats.tail(lat[:94] + [None] * 6, 95) == math.inf
    assert stats.tail(lat[:96] + [None] * 4, 95) == pytest.approx(0.95)


# ----------------------------------------------------------- operation counts


def test_train_flops_match_xla_at_smoke_widths():
    """6·N + 12·L·d·S against XLA's count of one forward and backward
    pass: XLA adds the norms, the softmax and the elementwise work, and
    counts the causal scores in full, so the formula stays a little
    below it.  One layer: XLA counts the body of the layers' scan once."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.drivers.train import program_config
    from repro.models import model as M

    one = dict(tiny.GRANITE, num_hidden_layers=1)
    cfg = program_config(one).replace(remat="none")
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    for seq in (32, 256):
        batch = {k: jax.ShapeDtypeStruct((2, seq), jnp.int32)
                 for k in ("tokens", "labels")}
        grad = jax.jit(jax.grad(lambda p, b: M.loss_fn(cfg, p, b)[0]))
        ca = grad.lower(params, batch).compile().cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        mine = flops.train_flops_per_token(one, seq) * 2 * seq
        assert 0.85 <= mine / ca["flops"] <= 1.0


def test_csr_dot_bytes_match_xla():
    """XLA counts the whole weight vector as the gather's operand; past
    that, the indices, values, gathered weights and outputs the formula
    counts are the least it touches."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    b, k, d = 1024, 64, 1_000_000
    ca = ops.csr_dot.lower(jax.ShapeDtypeStruct((b, k), jnp.int32),
                           jax.ShapeDtypeStruct((b, k), jnp.float32),
                           jax.ShapeDtypeStruct((d,), jnp.float32)
                           ).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mine = flops.csr_dot_bytes(b, k)
    assert 1.0 <= (ca["bytes accessed"] - 4 * d) / mine <= 1.5
    assert flops.csr_dot_flops(b, k) <= ca["flops"]


def test_roofline_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert flops.roofline_seconds(0.0, 819e9, p) == pytest.approx(1.0)
    assert flops.roofline_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# ----------------------------------------------------------------- refusals


def test_a_run_refuses_a_device_that_is_not_a_tpu(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert "TPU" in out.err
    assert not [ln for ln in out.out.splitlines() if ln.startswith("{")]


def test_a_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "system under test" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# ---------------------------------------------------------- found by name


def test_every_entry_resolves_its_files_by_name():
    bench = harness.load_benchmark(ROOT)
    assert bench["paths"] == ["benchmarks/chip"]
    for w in bench["workloads"]:
        cell = harness.resolve_cell(bench, w["name"])
        harness.driver_module(cell.traffic["driver"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m in cell.per_layer)
    for c in bench["configs"]:
        with open(ROOT / c["file"]) as f:
            stated = json.load(f)
        assert stated["name"] == c["name"]
        assert set(c["reduced"]) <= set(stated)


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A configuration, a traffic mix and a per-layer metric are added
    by writing files and entries; no file already there changes."""
    bench_dir = tmp_path / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    bench = harness.load_benchmark(ROOT)
    (bench_dir / "configs" / "svm-webspam.json").write_text(json.dumps({
        "name": "svm-webspam", "num_features": 16_609_143,
        "num_records": 65_536, "nnz_min": 3_000, "nnz_max": 8_000,
        "margin_dtype": "float32"}))
    with open(bench_dir / "traffic" / "score-lirs-tier25.json") as f:
        mix = json.load(f)
    mix["tier_fraction"] = 1.0
    (bench_dir / "traffic" / "score-lirs-resident.json").write_text(
        json.dumps(mix))
    (bench_dir / "metrics" / "svm_batches.py").write_text(
        "def read(w):\n    return w.counts['batches']\n")
    bench["configs"].append({"name": "svm-webspam", "source": "x",
                             "file": str(bench_dir / "configs" /
                                         "svm-webspam.json"),
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "svm-webspam.score-lirs-resident",
                               "config": "svm-webspam",
                               "traffic": "score-lirs-resident", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "svm_batches", "unit": "batches",
                               "better": "higher", "source": "program_counter",
                               "layer": "input pipeline",
                               "moves": "svm_records_per_s",
                               "workloads": ["svm-webspam.score-lirs-resident"]})
    for m in bench["end_to_end"]:
        if m["name"] == "svm_records_per_s":
            m["workloads"].append("svm-webspam.score-lirs-resident")
    cell = harness.resolve_cell(bench, "svm-webspam.score-lirs-resident",
                                bench_dir=bench_dir)
    assert cell.config["nnz_max"] == 8_000
    assert cell.traffic["tier_fraction"] == 1.0
    assert cell.traffic["driver"] == "svm_score"
    window = harness.Window(setup_s=1.0, attempted=1, failed=0,
                            counts={"batches": 7, "records": 70,
                                    "window_s": 1.0}, checks=[], memory={})
    got = harness.compute_metrics(window, cell.per_layer, bench_dir)
    assert got == {"svm_batches": {"value": 7.0, "unit": "batches"}}
    after = {p: p.read_bytes() for p in before}
    assert after == before


# --------------------------------------------------- cells end to end, tiny


@pytest.mark.parametrize("cell", CELLS)
def test_a_tiny_cell_runs_correct_with_nothing_compiled_in_its_window(
        small, cell):
    line = small(cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = harness.load_benchmark(ROOT)
    wanted = {m["name"] for m in bench["end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == wanted
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


def _broken_train_step(monkeypatch, how):
    from repro.models import model as M
    from repro.train import loop

    real_make = loop.make_train_step
    if how == "unchanged_state":
        def make(cfg, optimizer, *a, **k):
            step = real_make(cfg, optimizer, *a, **k)

            def broken(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return broken
        monkeypatch.setattr(loop, "make_train_step", make)
    elif how == "half_batch":
        real_loss = M.loss_fn

        def loss(cfg, params, batch, *a, **k):
            half = {n: x[: x.shape[0] // 2] for n, x in batch.items()}
            return real_loss(cfg, params, half, *a, **k)
        monkeypatch.setattr(M, "loss_fn", loss)


def _broken_margins(monkeypatch):
    from repro.svm.dcd import DCDSolver

    real = DCDSolver.margins_csr

    def altered(self, csr):
        m = np.array(real(self, csr))
        m[0] = -m[0] + 1.0
        return m
    monkeypatch.setattr(DCDSolver, "margins_csr", altered)


def _broken_decode(monkeypatch, how):
    import jax.numpy as jnp

    from repro.serve import engine

    real = engine._programs

    def programs(cfg):
        prefill, write_slot, decode = real(cfg)

        def broken(params, cache, toks):
            arena, logits = decode(params, cache, toks)
            if how == "unchanged_state":
                return cache, logits
            return arena, jnp.roll(logits, 1, axis=-1)   # altered token
        return prefill, write_slot, broken
    monkeypatch.setattr(engine, "_programs", programs)


@pytest.mark.parametrize("cell,fault", [
    (CELLS[0], "unchanged_state"),
    (CELLS[0], "half_batch"),
    (CELLS[1], "altered_answer"),
    (CELLS[2], "unchanged_state"),
    (CELLS[2], "altered_token"),
])
def test_a_broken_timed_path_comes_out_not_correct(small, monkeypatch, cell,
                                                   fault):
    """The faults each cell can have (one chip: no exchange between
    chips to leave out), planted under the timed path."""
    if cell == CELLS[0]:
        _broken_train_step(monkeypatch, fault)
    elif cell == CELLS[1]:
        _broken_margins(monkeypatch)
    else:
        _broken_decode(monkeypatch, fault)
    line = small(cell)
    assert line["correct"] is False
    failed = [k for k, c in line["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(tmp_path, monkeypatch, cpu_peaks,
                                           cell):
    """The reference in the precision below the configuration's, in the
    program's place, fails one of the cell's numbers at their limits."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    bench, bench_dir = tiny.make(tmp_path)
    c = harness.resolve_cell(bench, cell, bench_dir=bench_dir)
    harness.import_program()
    spec = harness.RunSpec(
        cell=c, seed=987654321987, seconds=1.0, trace=False, t_start=0.0,
        counter=harness.CompileCounter(), work_dir=harness.work_dir(tmp_path),
        peaks=peaks.PEAKS["cpu"], devices=jax.devices())
    driver = harness.driver_module(c.traffic["driver"])
    ev = driver.evidence(spec)
    assert all(x.ok for x in driver.checks(spec, ev))
    found = driver.control(spec, ev)
    control = [v for k, v in found.items() if k.startswith("control")][0]
    assert not all(x.ok for x in control)
