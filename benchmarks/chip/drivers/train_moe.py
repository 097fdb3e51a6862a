"""Training window for an MoE configuration over one chip's expert share:
the program's own ``Trainer.train()`` over the LIRS shuffler, the Belady
DRAM tier and the ``InputPipeline``, as ``drivers/train.py`` runs it for
a dense one, with this configuration's reference, weights, corpus and
operation count.

The corpus is the bigram process of ``data.token_rows`` with its uniform
draws (row starts, successor tables, fresh tokens) taken from a Zipf law
over the vocabulary slice, so routing is as uneven as text makes it.
The window adds the program's slot counters (``moe_held_slots``,
``moe_max_expert_slots``) from the trainer's per-step metrics, and a
traced window the device time of the ops under ``moe/`` and of the
grouped expert matmuls among them.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
import re
import shutil
from typing import Any, Callable, Dict, List
from unittest import mock

import numpy as np

from benchmarks.chip import data, harness, moe_flops, tracing, trace_reduce
from benchmarks.chip.drivers import train
from benchmarks.chip.reference import qwen2_moe as ref

BIAS_SCALE = 0.02  # q/k/v biases drawn as the embedding is


# ---------------------------------------------------------------- inputs


def zipf_token_rows(seed: int, records: int, seq_len: int, vocab: int,
                    a: float) -> np.ndarray:
    """``(records, seq_len + 1)`` int32 rows of ``data.token_rows``'s
    bigram corpus, its uniform draws replaced by draws of id ``r - 1``
    with probability ∝ ``r^-a`` over ``r = 1..vocab``."""
    g = data.rng(seed, 1)
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -a)
    cdf /= cdf[-1]

    def draw(size):
        return np.minimum(np.searchsorted(cdf, g.random(size)), vocab - 1)

    trans = draw((vocab, data.TOKEN_CHOICES))
    follow = g.random((records, seq_len)) < data.TOKEN_FOLLOW
    choice = g.integers(0, data.TOKEN_CHOICES, size=(records, seq_len))
    fresh = draw((records, seq_len))
    rows = np.empty((records, seq_len + 1), np.int64)
    rows[:, 0] = draw(records)
    for t in range(seq_len):
        nxt = trans[rows[:, t], choice[:, t]]
        rows[:, t + 1] = np.where(follow[:, t], nxt, fresh[:, t])
    return rows.astype(np.int32)


def make_params(c: Dict[str, Any], seed: int):
    """``weights.make_params`` for this layout: fan-ins from the
    reference (an expert's ``w_out`` is over its own width), q/k/v biases
    at ``BIAS_SCALE``, norm scales 0, built in one jitted call."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.weights import STREAM

    flat, treedef = jax.tree_util.tree_flatten_with_path(ref.layout(c))
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    shapes = [tuple(x.shape) for _, x in flat]

    def scale(name):
        if name == "['embed']" or name.endswith(("['bq']", "['bk']", "['bv']")):
            return BIAS_SCALE
        return 1.0 / math.sqrt(ref.fan_in(name, c))

    def build(key):
        leaves = []
        for i, (name, shape) in enumerate(zip(names, shapes)):
            if "norm" in name:
                leaves.append(jnp.zeros(shape, jnp.float32))
                continue
            x = jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
            leaves.append(scale(name) * x)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(data.jax_key(seed, STREAM))


# --------------------------------------------------------------- program


MOE_IMPLS = ("dense", "ragged")


def program_config(c: Dict[str, Any]):
    """The program's configuration for the file: its preset, cut to the
    file's depth, vocabulary slice and expert share and run as the file
    assumes (on the dense path, each held expert with a whole group's
    capacity, so no slot is dropped); refused where any other size or
    setting the file states is not what the program would run."""
    from repro.configs import get_config

    if c["moe_impl"] not in MOE_IMPLS:
        raise harness.BenchError(f"moe_impl {c['moe_impl']!r}: the program's "
                                 f"MoE paths are {MOE_IMPLS}")
    cfg = get_config(c["arch"], smoke=bool(c.get("smoke")))
    m = cfg.moe
    path = {"impl": c["moe_impl"], "group_size": c["moe_group_size"]}
    if c["moe_impl"] == "dense":
        path["capacity_factor"] = m.num_experts / m.experts_per_token
    cfg = cfg.with_layers(c["num_hidden_layers"]).replace(
        vocab_size=c["vocab_size"], attn_impl=c["attn_impl"],
        remat=c["remat"],
        moe=dataclasses.replace(m, held_experts=c["num_experts"],
                                first_expert=c["first_expert"], **path))
    m = cfg.moe
    ran = {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.kq_dim,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "decoder_sparse_step": 1 if all(
            k == "moe" for p, _ in cfg.stages for k in p) else 0,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings, "qkv_bias": cfg.qkv_bias,
        "hidden_act": "silu" if cfg.activation == "swiglu" else cfg.activation,
        "router_experts": m.num_experts, "num_experts": m.held,
        "first_expert": m.first_expert,
        "num_experts_per_tok": m.experts_per_token,
        "moe_intermediate_size": m.d_ff_expert,
        "num_shared_experts": m.num_shared_experts,
        "shared_expert_intermediate_size": m.d_ff_shared,
        "shared_expert_gate": m.num_shared_experts > 0,
        "norm_topk_prob": m.norm_topk_prob,
        "router_aux_loss_coef": m.aux_loss_coef,
        "attn_impl": cfg.attn_impl, "remat": cfg.remat,
        "compute_dtype": cfg.dtype, "param_dtype": cfg.param_dtype,
    }
    wrong = {k: (c[k], v) for k, v in ran.items() if c[k] != v}
    if wrong:
        raise harness.BenchError(f"the program runs {c['arch']} with "
                                 f"(file, program) {wrong}")
    return cfg


def check_layout(cfg, c) -> None:
    import jax

    from repro.models import model as M

    prog = jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    mine = ref.layout(c)
    same = jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(
        mine) and all(a.shape == b.shape for a, b in zip(
            jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(mine)))
    if not same:
        raise harness.BenchError("the program's parameter layout is not the "
                                 "reference's")


# ----------------------------------------------------------------- trace

STEP_PROGRAM = "jit_train_step"
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def hlo_scopes(text: str) -> Dict[str, str]:
    """Each instruction's ``op_name`` metadata (its name scope) in a
    compiled module's text."""
    return {m.group(1): m.group(2) for m in _HLO_LINE.finditer(text)}


def is_gmm(scope: str, op: str = "") -> bool:
    """A grouped expert matmul, forward or backward: on a TPU a
    ``ragged-dot`` custom call (which the compiler names so and gives no
    scope), elsewhere the ``ragged_dot`` or the ``dot_general`` a backend
    expands it to, under ``moe/experts``."""
    if op.startswith("ragged-dot"):
        return "metadata" not in op
    return "moe/experts" in scope and ("ragged_dot" in scope
                                       or "dot_general" in scope)


def is_moe(scope: str, op: str = "") -> bool:
    """An op of the MoE layer: under a ``moe/`` scope, or one of the
    TPU's grouped-matmul custom calls and their metadata."""
    return "moe/" in scope or op.startswith("ragged-dot")


def moe_ops(flat: Dict[str, Any], step_scopes: Callable[[], Dict[str, str]]
            ) -> Dict[str, float]:
    """Device seconds, over the traced span of a flattened trace
    (``trace_reduce.load_xplane``), of the train step's MoE ops
    (``is_moe``) and of the grouped expert matmuls among them
    (``is_gmm``).  A TPU's trace names each op but gives no scope: an
    op's scope is its ``op_name`` in the step's compiled text
    (``step_scopes()``, asked for once)."""
    span = [h for h in flat["host"] if h[0] == trace_reduce.TRACED_SPAN][0]
    lo, hi = float(span[1]), float(span[1]) + float(span[2])
    scopes = None
    moe_s = gmm_s = 0.0
    for _plane, mods, ops in flat["device"]:
        steps = sorted((float(s), float(s) + float(d)) for n, s, d in mods
                       if trace_reduce.program_name(n)[0] == STEP_PROGRAM)
        starts = [a for a, _ in steps]
        for name, start, dur in ops:
            start = float(start)
            i = bisect.bisect_right(starts, start) - 1
            if not lo <= start < hi or i < 0 or start >= steps[i][1]:
                continue  # outside the span, or not the train step's
            if scopes is None:
                scopes = step_scopes()
            op = trace_reduce.op_name(name)
            scope = scopes.get(op, "")
            if is_moe(scope, op):
                moe_s += float(dur) / 1e9
                if is_gmm(scope, op):
                    gmm_s += float(dur) / 1e9
    devices = max(1, len(flat["device"]))
    return {"moe_s": moe_s / devices, "gmm_s": gmm_s / devices}


class MoETracer(tracing.Tracer):
    """The harness's tracer, whose reduction also reads the MoE ops'
    device time from the trace before deleting it."""

    def __init__(self, log_dir, step_scopes: Callable[[], Dict[str, str]]):
        super().__init__(log_dir)
        self.step_scopes = step_scopes

    def reduce(self) -> Dict[str, Any]:
        try:
            flat = trace_reduce.load_xplane(str(self.log_dir))
            reduced = trace_reduce.reduce_trace(flat)
            reduced["moe"] = moe_ops(flat, self.step_scopes)
            return reduced
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


# --------------------------------------------------------------- session


class Session(train.Session):
    """``train.Session`` with this configuration's program check, corpus
    and weights, and the window's slot counters and MoE trace."""

    def __init__(self, spec: harness.RunSpec):
        import jax
        import jax.numpy as jnp

        from repro.core.readpath import ReadPathConfig, build_data_plane
        from repro.storage.record_store import RecordStore
        from repro.train import loop
        from repro.train.optimizer import AdamWConfig

        self.spec = spec
        c, t = spec.cell.config, spec.cell.traffic
        self.c, self.t = c, t
        seed = spec.seed
        self.cfg = program_config(c)
        check_layout(self.cfg, c)
        seq, n, batch = t["seq_len"], t["records"], t["batch"]
        self.rows = zipf_token_rows(seed, n, seq, c["vocab_size"], t["zipf_a"])
        path = str(spec.work_dir / "corpus.rrec")
        data.write_token_corpus(path, self.rows)
        self.store = RecordStore(path)
        self.shuffler = loop.make_shuffler(t["shuffler"], n, batch, seed=seed)
        record_bytes = 4 * (seq + 1)
        self.plane = build_data_plane(self.store, ReadPathConfig(
            shuffler=self.shuffler,
            cache_budget_bytes=int(t["tier_fraction"] * n * record_bytes),
            lookahead=t["lookahead"], eviction_policy=t["eviction_policy"],
            workers=t["io_workers"],
        ))
        self.feed = train.Feed(self.plane, seq, spec.trace)

        def init_state(cfg, rng, optimizer, compressor=None):
            params = make_params(c, seed)
            return jax.jit(lambda p: {
                "params": p, "opt": optimizer.init(p),
                "step": jnp.zeros((), jnp.int32)})(params)

        with mock.patch.object(loop, "init_train_state", init_state):
            self.trainer = loop.Trainer(
                self.cfg, self.feed.fetch, self.shuffler,
                loop.TrainLoopConfig(epochs=2, max_steps=t["check_steps"],
                                     seed=seed & 0x7FFFFFFF),
                opt_cfg=AdamWConfig(lr=t["lr"], warmup_steps=t["warmup_steps"]),
                batch_iter_fn=self.feed.batches,
                epoch_hook=self._epoch_done,
            )
        self.checked = self._first_steps()

    def window(self) -> Dict[str, Any]:
        step, t = self.trainer.step_fn, self.t

        def step_scopes():
            batch = {k: np.zeros((t["batch"], t["seq_len"]), np.int32)
                     for k in ("tokens", "labels")}
            return hlo_scopes(step.lower(self.trainer.state, batch)
                              .compile().as_text())

        first = len(self.trainer.history)
        with mock.patch.object(tracing, "Tracer",
                               lambda d: MoETracer(d, step_scopes)):
            w = super().window()
        steps = self.trainer.history[first:]
        held = sum(h["moe_held_slots"] for h in steps)
        per_step = held / max(1, len(steps))
        counts = w["counts"]
        counts["moe_held_slots"] = held
        # the busiest held expert over an even share of the step's slots
        counts["moe_imbalance_sum"] = sum(
            h["moe_max_expert_slots"] * self.c["num_experts"] / h["moe_held_slots"]
            for h in steps if h["moe_held_slots"])
        if counts["tokens"]:
            counts["flops_per_token"] = moe_flops.train_flops_per_token(
                self.c, t["seq_len"], held / counts["tokens"])
        if w["trace"] is not None:
            w["trace"]["moe"].update(
                steps=w["trace"]["programs"].get(STEP_PROGRAM, {}).get("calls", 0),
                gmm_flops=moe_flops.gmm_flops(self.c, per_step),
                gmm_bytes=moe_flops.gmm_bytes(self.c, per_step),
                peaks=dict(self.spec.peaks))
        return w


# ---------------------------------------------------------------- checks


def reference_steps(spec, ev, nm=ref.F32, fault=None):
    """The reference's first steps over the batches the program took."""
    c, t = spec.cell.config, spec.cell.traffic
    batches = [(ev["rows"][i][:, :-1], ev["rows"][i][:, 1:])
               for i in ev["checked"]["ids"][:t["check_steps"]]]
    return ref.train_steps(
        lambda: make_params(c, spec.seed), batches, c,
        ref.AdamW(lr=t["lr"], warmup_steps=t["warmup_steps"]), nm=nm,
        fault=fault)


def checks(spec, ev) -> List[harness.Check]:
    """Every window batch against the records as generated, and the
    checked steps against the reference (``drivers/train.py``'s
    comparison)."""
    t = spec.cell.traffic
    rows, kept, order = ev["rows"], ev["kept"], ev["order"]
    mismatched = 0
    for k, batch in kept.items():
        want = rows[order[k]]
        if not (np.array_equal(batch["tokens"], want[:, :-1])
                and np.array_equal(batch["labels"], want[:, 1:])):
            mismatched += 1
    r = reference_steps(spec, ev)
    return [
        harness.Check("batch_mismatch", float(mismatched), 0.0),
        harness.Check("batches_checked_short",
                      float(max(0, ev["window"]["counts"]["steps"]
                                - len(kept))), 0.0),
    ] + compare(ev["checked"], r, t["limits"])


def compare(prog, r, lim) -> List[harness.Check]:
    """``train.compare`` over the gaps the traffic gives a limit.  The
    loss gap has none here: float8 moves the cell's loss by barely twice
    what the program's bfloat16 does, too little to hold a limit
    between, so it is printed, not checked."""
    found = train.gaps(prog, r)
    print(f"[check] losses {prog['losses']!r} reference {r['losses']!r}; "
          + ", ".join(f"{k} {v!r}" for k, v in found.items() if k not in lim)
          + " (no limit)", flush=True)
    return [harness.Check(k, v, lim[k]) for k, v in found.items() if k in lim]


def control(spec, ev) -> Dict[str, List[harness.Check]]:
    """Readings of the control (the reference in float8 in the program's
    place) and of the fault of half the batch left out."""
    lim = spec.cell.traffic["limits"]
    r = reference_steps(spec, ev)
    return {
        "control_fp8": compare(reference_steps(spec, ev, nm=ref.FP8), r, lim),
        "fault_half_batch": compare(
            reference_steps(spec, ev, fault="half_batch"), r, lim),
    }


def change_norms(spec, after) -> np.ndarray:
    import jax

    diff = jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
    return np.asarray(diff(jax.device_put(after),
                           make_params(spec.cell.config, spec.seed)))


def route_flip_share(spec, ev) -> float:
    """The share of the first checked batch's token-slots whose expert is
    not among the reference's top k at the same layer, with the seed's
    weights: the program's routes are read from its own forward pass."""
    import jax
    import jax.numpy as jnp

    from repro.layers import moe as moe_lib
    from repro.models import model as M

    c = spec.cell.config
    row = ev["rows"][ev["checked"]["ids"][0]]
    tokens, labels = jnp.asarray(row[:, :-1]), jnp.asarray(row[:, 1:])
    params = make_params(c, spec.seed)
    seen: List[np.ndarray] = []
    real = moe_lib.route

    def recording(p, x, moe):
        gate, ids, aux = real(p, x, moe)
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), ids)
        return gate, ids, aux

    cfg = program_config(c)
    with mock.patch.object(moe_lib, "route", recording):
        jax.block_until_ready(jax.jit(lambda p: M.loss_fn(
            cfg, p, {"tokens": tokens, "labels": labels})[0])(params))
        jax.effects_barrier()
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(r) for r in jax.jit(
            lambda p: ref.routes(p, tokens, c))(params)]
    del params
    k = c["num_experts_per_tok"]
    # the dense path routes the sequence in groups: compare token by token
    seen = [a.reshape(-1, k) for a in seen]
    want = [b.reshape(-1, k) for b in want]
    if [a.shape for a in seen] != [b.shape for b in want]:
        return math.nan  # the program routed other tokens than the batch's
    same = sum(int((a[:, :, None] == b[:, None, :]).any(-1).sum())
               for a, b in zip(seen, want))
    return 1.0 - same / (len(want) * want[0].size)


def evidence(spec: harness.RunSpec) -> Dict[str, Any]:
    """Set-up, the window and what the checks need, with the program's
    state freed."""
    s = Session(spec)
    w = s.window()
    ev = {"window": w, "checked": s.checked, "rows": s.rows,
          "kept": dict(s.feed.kept),
          "order": [np.array(i) for i in s.feed.order]}
    s.close()
    del s
    ev["checked"]["change_norms"] = change_norms(
        spec, ev["checked"].pop("params_after"))
    return ev


def run(spec: harness.RunSpec) -> harness.Window:
    ev = evidence(spec)
    w = ev["window"]
    found = checks(spec, ev)
    steps = w["counts"]["steps"]
    return harness.Window(
        setup_s=w["setup_s"], attempted=steps, failed=0, counts=w["counts"],
        checks=found, trace=w["trace"], memory=w["memory"],
        notes={"window_compiles": w["window_compiles"],
               "checked_losses": ev["checked"]["losses"],
               "route_flip_share": route_flip_share(spec, ev)},
    )
