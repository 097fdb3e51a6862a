"""CPU tests of the MoE training cell: its driver end to end at a tiny
size, sound and with its timed path broken, its control, its refusals,
its corpus, its operation counts and its metric readers.

    python -m pytest benchmarks/chip/tests/test_train_moe.py
"""
from __future__ import annotations

import json
import os
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import harness, moe_flops, peaks, run, trace_reduce
from benchmarks.chip.drivers import train_moe
from benchmarks.chip.tests import tiny

HERE = Path(__file__).resolve().parent
BENCH_DIR = HERE.parent
ROOT = BENCH_DIR.parents[1]
CELL = "qwen2-moe-a2.7b.train-8k-lirs-ep6"

# the program's small qwen2-moe variant with a share of 4 of its 12
# experts, from the fifth on, and a slice of 128 of its 512 ids
QWEN = {
    "name": "qwen2-moe-tiny", "arch": "qwen2-moe-a2.7b", "smoke": True,
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 128,
    "num_hidden_layers": 2, "decoder_sparse_step": 1, "router_experts": 12,
    "num_experts": 4, "first_expert": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "shared_expert_intermediate_size": 128, "shared_expert_gate": True,
    "norm_topk_prob": False, "router_aux_loss_coef": 0.001, "qkv_bias": True,
    "hidden_act": "silu", "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "compute_dtype": "bfloat16",
    "param_dtype": "float32", "moe_impl": "dense", "moe_group_size": 16,
    "attn_impl": "blocked",
    "remat": "full",
}


def _traffic():
    with open(BENCH_DIR / "traffic" / "train-8k-lirs-zipf.json") as f:
        t = json.load(f)
    # the smoke widths round differently from the published ones: the
    # limits sit between the CPU readings of sound runs (gradient and
    # change gaps up to 6.9e-3, 8.9e-3 over five seeds, and 5.2e-3,
    # 1.14e-2 on the ragged path) and of the float8 control (at least
    # 2.6e-2 on the gradient), as tiny.py's granite limits do; the loss
    # gap is reported and not checked, as in the cell
    t.update(records=64, seq_len=32, batch=2, io_workers=2, limits={
        "grad_norm_gap": 1.2e-2, "change_norm_gap": 2.5e-2})
    return t


def make(tmp: Path):
    """The tiny MoE cell under ``tmp``: the parsed ``BENCHMARK.json`` and
    the directory its files are found in."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench_dir = tmp / "bench"
    (bench_dir / "traffic").mkdir(parents=True)
    (bench_dir / "configs").mkdir()
    os.symlink(BENCH_DIR / "metrics", bench_dir / "metrics")
    (bench_dir / "traffic" / "train-moe-tiny.json").write_text(
        json.dumps(_traffic()))
    path = bench_dir / "configs" / "qwen2-moe-tiny.json"
    path.write_text(json.dumps(QWEN))
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    bench["configs"] = [{"name": QWEN["name"], "file": str(path)}]
    bench["workloads"] = [dict(cell, config=QWEN["name"],
                               traffic="train-moe-tiny")]
    return bench, bench_dir


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})


@pytest.fixture
def small(tmp_path, monkeypatch, cpu_peaks):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    bench, bench_dir = make(tmp_path)

    def go(**kw):
        import jax

        return run.run(tiny.args(CELL, **kw), devices=jax.devices(),
                       bench=bench, bench_dir=bench_dir, work=tmp_path)

    return go


def _spec(tmp_path, seed=987654321987):
    import jax

    bench, bench_dir = make(tmp_path)
    c = harness.resolve_cell(bench, CELL, bench_dir=bench_dir)
    harness.import_program()
    return harness.RunSpec(
        cell=c, seed=seed, seconds=1.0, trace=False, t_start=0.0,
        counter=harness.CompileCounter(), work_dir=harness.work_dir(tmp_path),
        peaks=peaks.PEAKS["cpu"], devices=jax.devices())


# ------------------------------------------------------------ end to end


def test_the_tiny_cell_runs_correct_with_nothing_compiled_in_its_window(
        small, capsys):
    line = small()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # the loss gap is printed, not checked
    assert set(line["checks"]) == {"batch_mismatch", "batches_checked_short",
                                   "grad_norm_gap", "change_norm_gap"}
    out = capsys.readouterr().out
    assert "compilations inside the window: 0" in out
    assert any("loss_gap" in ln and "(no limit)" in ln
               for ln in out.splitlines())
    flips = [ln for ln in out.splitlines() if "route_flip_share" in ln]
    assert flips and 0.0 <= float(flips[0].split()[-1]) < 0.5


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_a_broken_timed_path_comes_out_not_correct(small, monkeypatch, fault):
    from repro.models import model as M
    from repro.train import loop

    if fault == "unchanged_state":
        real_make = loop.make_train_step

        def make_step(cfg, optimizer, *a, **k):
            step = real_make(cfg, optimizer, *a, **k)

            def broken(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return broken
        monkeypatch.setattr(loop, "make_train_step", make_step)
    else:
        real_loss = M.loss_fn

        def loss(cfg, params, batch, *a, **k):
            half = {n: x[: x.shape[0] // 2] for n, x in batch.items()}
            return real_loss(cfg, params, half, *a, **k)
        monkeypatch.setattr(M, "loss_fn", loss)
    line = small()
    assert line["correct"] is False
    assert [k for k, c in line["checks"].items()
            if not c["value"] <= c["limit"]], line["checks"]


def test_the_control_and_the_fault_come_out_not_correct(tmp_path, monkeypatch,
                                                        cpu_peaks):
    """The reference in float8 in the program's place, and half the
    batch left out, each fail one of the cell's numbers at its limits."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    spec = _spec(tmp_path)
    ev = train_moe.evidence(spec)
    assert all(x.ok for x in train_moe.checks(spec, ev))
    for name, found in train_moe.control(spec, ev).items():
        assert not all(x.ok for x in found), name


# -------------------------------------------------------------- refusals


@pytest.mark.parametrize("key,value", [
    ("moe_intermediate_size", 64),     # a width the program does not run
    ("norm_topk_prob", True),          # gates the program does not take
    ("router_experts", 16),            # a router of another width
    ("qkv_bias", False),
    ("moe_impl", "sparse"),            # a path the program does not have
])
def test_the_program_refuses_a_file_it_would_not_run(key, value):
    harness.import_program()
    with pytest.raises(harness.BenchError, match=key):
        train_moe.program_config(dict(QWEN, **{key: value}))


def test_the_program_runs_the_files_share_and_slice():
    harness.import_program()
    cfg = train_moe.program_config(QWEN)
    assert (cfg.moe.held, cfg.moe.first_expert, cfg.moe.num_experts) == (4, 4, 12)
    assert cfg.vocab_size == 128 and cfg.num_layers == 2
    # the file names the MoE path; dense gets a whole group's capacity
    assert (cfg.moe.impl, cfg.moe.group_size, cfg.moe.capacity_factor) == (
        "dense", 16, 12 / 4)
    assert train_moe.program_config(dict(QWEN, moe_impl="ragged")).moe.impl \
        == "ragged"
    with open(BENCH_DIR / "configs" / "qwen2-moe-a2.7b.json") as f:
        full = json.load(f)
    cfg = train_moe.program_config(full)
    assert (cfg.moe.held, cfg.moe.num_experts, cfg.vocab_size) == (10, 60, 25323)
    for key in full["reduced"]:
        assert full["published"][key] != full[key]


# ------------------------------------------------------------------ inputs


def test_zipf_rows_are_seeded_skewed_and_inside_the_slice():
    a = train_moe.zipf_token_rows(12345678901234, 32, 256, 1000, 1.0)
    b = train_moe.zipf_token_rows(12345678901234, 32, 256, 1000, 1.0)
    assert a.shape == (32, 257) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 1000
    counts = np.bincount(a.ravel(), minlength=1000)
    # id 0 is the likeliest draw: far above a uniform id's share
    assert counts[0] > 10 * a.size / 1000


def test_weights_follow_the_layout_and_the_seed():
    import jax

    harness.import_program()
    p = train_moe.make_params(QWEN, 5)
    q = train_moe.make_params(QWEN, 5)
    flat = jax.tree_util.tree_leaves_with_path(p)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(q)))
    cfg = train_moe.program_config(QWEN)
    train_moe.check_layout(cfg, QWEN)
    for path, x in flat:
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            assert not np.any(np.asarray(x))
        elif name.endswith("['moe']['w_out']"):
            # an expert's w_out is over its own width, 32
            assert float(np.std(np.asarray(x))) == pytest.approx(
                0.88 / np.sqrt(32), rel=0.15)


# ------------------------------------------------------- counts and readers


def test_gmm_counts_only_the_held_slots():
    c = dict(QWEN)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    assert moe_flops.gmm_flops(c, 100) == 6 * 3 * d * f * 100
    assert moe_flops.gmm_flops(c, 0) == 0
    # the weights are read and written whatever the slots
    assert moe_flops.gmm_bytes(c, 0) == 9 * c["num_hidden_layers"] * \
        c["num_experts"] * d * f * 2
    per_token = moe_flops.train_flops_per_token(c, 32, 0.0)
    assert moe_flops.train_flops_per_token(c, 32, 2.0) == pytest.approx(
        per_token + 6 * 3 * d * f * 2)


def test_the_layer_scopes_its_ops_for_the_trace():
    """The compiled step names each MoE op's scope: routing under
    ``moe/route``, the grouped matmuls (forward and backward) under
    ``moe/experts``, the shared expert under ``moe/shared``."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    harness.import_program()
    cfg = train_moe.program_config(QWEN)
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(jax.grad(lambda p, b: M.loss_fn(cfg, p, b)[0])).lower(
        params, batch).compile().as_text()
    scopes = list(train_moe.hlo_scopes(text).values())
    for part in ("moe/route", "moe/experts", "moe/shared"):
        assert any(part in s for s in scopes), part
    assert any(train_moe.is_gmm(s) for s in scopes)
    # a TPU names its grouped matmuls and gives them no scope
    assert train_moe.is_gmm("ragged-dot-none", "ragged-dot-none.8")
    assert train_moe.is_moe("ragged-dot-metadata", "ragged-dot-metadata.1")
    assert not train_moe.is_gmm("ragged-dot-metadata", "ragged-dot-metadata.1")
    assert not train_moe.is_moe("jit(train_step)/attn/dot_general", "fusion.3")


def test_the_moe_ops_are_the_train_steps_in_the_traced_span(tmp_path,
                                                            monkeypatch):
    """Ops of the train step inside the traced span count by their scope
    in the step's compiled text; a TPU's grouped matmuls, which have
    none, by their name; another program's ops and ops past the span
    not at all."""
    span = [trace_reduce.TRACED_SPAN, 1000, 100]
    dev = ["/device:TPU:0",
           [["jit_train_step(5)", 1000, 60], ["jit__lambda(7)", 1070, 20]],
           [["%fusion.1 = bf16[8] fusion(...)", 1000, 10],
            ["%ragged-dot-none.3 = bf16[8] custom-call(...)", 1010, 20],
            ["%ragged-dot-metadata = (s32[5]) custom-call(...)", 1030, 2],
            ["%fusion.2 = bf16[8] fusion(...)", 1040, 10],
            ["%fusion.1 = bf16[8] fusion(...)", 1075, 5],
            ["%fusion.1 = bf16[8] fusion(...)", 1200, 5]]]
    scopes = {"fusion.1": "jit(train_step)/while/body/moe/route/top_k",
              "fusion.2": "jit(train_step)/while/body/attn/dot_general",
              "ragged-dot-none.3": "ragged-dot-none",
              "ragged-dot-metadata": "ragged-dot-metadata"}
    flat = {"device": [dev], "host": [span]}
    asked = []
    got = train_moe.moe_ops(flat, lambda: asked.append(1) or scopes)
    assert got["moe_s"] == pytest.approx(32e-9)
    assert got["gmm_s"] == pytest.approx(20e-9)
    assert asked == [1]
    # the tracer's reduction adds them to the harness's, and deletes the
    # trace
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda d: flat)
    (tmp_path / "trace").mkdir()
    r = train_moe.MoETracer(tmp_path / "trace", lambda: scopes).reduce()
    assert r["moe"] == got and r["programs"]["jit_train_step"]["calls"] == 1
    assert not (tmp_path / "trace").exists()


def _window(counts=None, trace=None):
    return harness.Window(setup_s=1.0, attempted=1, failed=0,
                          counts=counts or {}, checks=[], memory={},
                          trace=trace)


def test_the_readers_read_the_window_and_nothing_where_it_has_none():
    p = peaks.peaks_for("TPU v5 lite")
    moe = {"moe_s": 0.3, "gmm_s": 0.1, "steps": 2, "gmm_flops": 1e12,
           "gmm_bytes": 1e9, "peaks": p}
    w = _window({"steps": 4, "moe_imbalance_sum": 6.0}, {"moe": moe})
    read = {n: harness.metric_reader(n) for n in
            ("moe_ms", "moe_gmm_roofline", "moe_load_imbalance")}
    assert read["moe_ms"](w) == pytest.approx(150.0)
    assert read["moe_gmm_roofline"](w) == pytest.approx(
        100 * (1e12 / 197e12) / 0.05)
    assert read["moe_load_imbalance"](w) == pytest.approx(1.5)
    # a dense cell's window, or a trace with no MoE op in it
    bare = _window({"steps": 4}, {"programs": {}})
    assert all(r(bare) is None for r in read.values())
    empty = _window({"steps": 4}, {"moe": dict(moe, moe_s=0.0, gmm_s=0.0)})
    assert read["moe_ms"](empty) is None
    assert read["moe_gmm_roofline"](empty) is None
