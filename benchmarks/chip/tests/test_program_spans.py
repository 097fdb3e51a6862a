"""CPU tests of ``program_spans``: the reduction of the program's ring,
the idle time put down to program spans, and a tiny traced run of each
cell with the ring on.

    python -m pytest benchmarks/chip/tests -p xdist -n 6
"""
from __future__ import annotations

import pytest

from benchmarks.chip import harness, program_spans, run, trace_reduce
from benchmarks.chip.tests import tiny
from benchmarks.chip.tests.test_chip_bench import CELLS, _trace


def _x(name, ts, dur, tid=1, **args):
    e = {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": tid}
    if args:
        e["args"] = args
    return e


def _events():
    """Two serve steps on the loop's thread, the second admitting a
    request, and a fetch on a producer thread that overlaps them."""
    return [
        _x("serve/step", 0, 100, step=0),
        _x("serve/decode", 10, 60),
        _x("serve/emit", 75, 20),
        _x("serve/step", 150, 200, step=1),
        _x("serve/admit", 155, 120, rid=3),
        _x("serve/features", 160, 10),
        _x("serve/prefill", 175, 90),
        _x("serve/decode", 280, 50),
        _x("pipeline/fetch", 50, 250, tid=2),
        {"name": "cache/evict", "ph": "i", "ts": 60, "tid": 2, "s": "t"},
    ]


def test_self_time_takes_out_the_children_on_the_same_thread():
    r = program_spans.reduce_events(_events())
    step = r["serve/step"]
    assert step["count"] == 2
    assert step["total_s"] == pytest.approx(300e-6)
    # 100 - 60 - 20, and 200 - 120 - 50: the fetch is another thread's
    assert step["self_s"] == pytest.approx(50e-6)
    assert r["serve/admit"]["self_s"] == pytest.approx(20e-6)
    assert r["serve/prefill"]["self_s"] == pytest.approx(90e-6)
    assert r["pipeline/fetch"] == {"count": 1, "total_s": pytest.approx(250e-6),
                                   "self_s": pytest.approx(250e-6)}
    assert "cache/evict" not in r


def test_host_gaps_run_from_one_span_end_to_the_next_start():
    evs = [_x("train/step", 0, 100), _x("train/dispatch", 0, 5),
           _x("train/step", 112, 100), _x("train/step", 230, 100)]
    assert program_spans.host_gaps(evs, "train/step") == pytest.approx(
        [12e-6, 18e-6])
    assert program_spans.host_gaps(evs[:2], "train/step") == []


def test_the_ring_counts_what_it_dropped():
    from repro.obs import trace

    rec = trace.enable(capacity_per_thread=4)
    for k in range(6):
        with trace.span("svm/pad", "svm"):
            pass
    trace.disable()
    probe = program_spans.Probe()
    probe.rec, probe.events = rec, rec.drain()
    got = probe.summary()
    assert got["dropped"] == 2
    assert got["spans"]["svm/pad"]["count"] == 4


def test_the_five_numbers_from_a_probe():
    probe = program_spans.Probe()
    probe.events = _events() + [
        _x("svm/margins", 400, 100), _x("svm/put", 410, 60),
        _x("svm/margins", 520, 100), _x("svm/put", 530, 40),
        _x("train/step", 700, 50), _x("train/step", 760, 50),
        _x("train/step", 830, 50)]
    probe.close_us = 1000.0
    probe.at_close = {"slot_steps": 30, "decode_steps": 4}
    probe.waits = [0.001 * k for k in range(1, 20)] + [None]
    got = probe.summary()
    assert got["serve_queue_wait_p95_ms"] == pytest.approx(19.0)
    assert got["serve_batch_occupancy"] == 7.5
    # self times 50 (steps) + 20 (admit) + 20 (emit) over two steps
    assert got["serve_step_host_ms"] == pytest.approx(45e-3)
    assert got["svm_put_ms"] == pytest.approx(50e-3)
    assert got["train_host_gap_ms"] == pytest.approx(15e-3)
    # one request in twenty never admitted: the p95 misses
    probe.waits[-2] = None
    assert probe.summary()["serve_queue_wait_p95_ms"] is None


def test_events_after_the_close_are_left_out():
    probe = program_spans.Probe()
    probe.events = [_x("train/step", 0, 10), _x("train/step", 20, 10),
                    _x("train/step", 5000, 10)]
    probe.close_us = 100.0
    assert probe.summary()["train_host_gap_ms"] == pytest.approx(10e-3)


def test_idle_goes_to_the_innermost_program_span_of_the_loop_thread():
    """The benchmark's fixture, with program spans on two threads: the
    loop's thread (which covers most of the traced span) takes the idle
    time, each part to its innermost span, and ``idle_gaps`` reads as it
    did without them."""
    flat = _trace()
    before = trace_reduce.reduce_trace(flat)
    program = [
        ["serve/step", 1000, 100, "loop"],
        ["serve/admit", 1025, 30, "loop"],
        ["serve/prefill", 1040, 10, "loop"],
        ["pipeline/fetch", 1030, 20, "producer"],
    ]
    got = dict(program_spans.program_gaps(flat, program))
    # device 0 idles 1030-1050 (admit, then prefill), 1080-1090 and
    # 1095-1100 (step alone); device 1 never: the mean over the two
    # halves each part
    assert got["serve/admit"] == pytest.approx(10 / 2 * 1e-9)
    assert got["serve/prefill"] == pytest.approx(10 / 2 * 1e-9)
    assert got["serve/step"] == pytest.approx((10 + 5) / 2 * 1e-9)
    assert program_spans.NO_SPAN not in got
    assert sum(got.values()) == pytest.approx(
        before["window_s"] - before["busy_s"])
    assert trace_reduce.reduce_trace(flat) == before
    uncovered = dict(program_spans.program_gaps(flat, program[2:3]))
    assert uncovered[program_spans.NO_SPAN] == pytest.approx(12.5e-9)


def test_innermost_segments_of_nested_spans():
    spans = [(0, 100, "a"), (10, 20, "b"), (30, 40, "c"), (35, 38, "d")]
    assert program_spans._innermost(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "a"), (30, 35, "c"),
        (35, 38, "d"), (38, 40, "c"), (40, 100, "a")]


@pytest.mark.parametrize("cell", CELLS)
def test_a_tiny_cell_reads_its_program_spans(tmp_path, monkeypatch, cell):
    """A tiny run of each cell with the ring on through its window (on
    the CPU, whose profiler trace has no device plane to reduce): the
    cell's numbers read as numbers, and the ring dropped nothing."""
    import jax

    from benchmarks.chip import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    bench, bench_dir = tiny.make(tmp_path)
    c = harness.resolve_cell(bench, cell, bench_dir=bench_dir)
    probe = program_spans.Probe()
    with probe.hooks(harness.driver_module(c.traffic["driver"])):
        line = run.run(tiny.args(cell, seconds=3.0), devices=jax.devices(),
                       bench=bench, bench_dir=bench_dir, work=tmp_path)
    assert line["correct"] is True, line["checks"]
    got = probe.summary()
    assert got["dropped"] == 0
    outer, wanted = {
        CELLS[0]: ("train/step", ["train_host_gap_ms"]),
        CELLS[1]: ("svm/margins", ["svm_put_ms"]),
        CELLS[2]: ("serve/step", ["serve_queue_wait_p95_ms",
                                  "serve_batch_occupancy",
                                  "serve_step_host_ms"]),
    }[cell]
    assert got["spans"][outer]["count"] > 1
    for k in wanted:
        assert isinstance(got[k], float) and got[k] >= 0, (k, got)
