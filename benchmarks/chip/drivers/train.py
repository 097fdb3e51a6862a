"""Training window: the program's own ``Trainer.train()`` over the LIRS
shuffler, the Belady DRAM tier and the ``InputPipeline``, into the
jitted train step.

Set-up builds one trainer, with weights made here from the seed, and
drives it through the configuration's first steps (``check_steps``)
with its own call and feed; those are the steps the reference follows.
The window then continues the same trainer from the next epoch, and
ends from this side: the batch iterator stops yielding at the deadline,
and the window closes when ``train()`` returns.  The rate is every
step's tokens over all of that time.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional
from unittest import mock

import numpy as np

from benchmarks.chip import data, flops, harness, tracing, weights
from benchmarks.chip.reference import granite as ref


class WindowClosed(Exception):
    """Raised from the trainer's epoch hook once the feed has stopped at
    the deadline: the window closes when ``train()`` unwinds."""


class Feed:
    """The trainer's batch source and fetch: the data plane's own, with
    the batches' record ids kept, each batch from ``keep_from`` on
    copied, and a deadline after which no batch is yielded."""

    def __init__(self, plane, seq: int, trace: bool):
        from repro.data.synthetic import decode_token_batch

        self.plane, self.seq = plane, seq
        self.decode = decode_token_batch
        self.trace = trace
        self.deadline: Optional[float] = None
        self.order: List[np.ndarray] = []   # ids of every batch yielded
        self.kept: Dict[int, Dict[str, np.ndarray]] = {}
        self.keep_from: Optional[int] = None
        self.fetched = 0
        self.stopped = False

    def batches(self, epoch: int):
        for idx in self.plane.batch_iter(epoch):
            if self.deadline is not None and time.perf_counter() >= self.deadline:
                self.stopped = True
                return
            self.order.append(np.array(idx))
            yield idx

    def fetch(self, idx):
        if self.trace:
            with tracing.span("fetch"):
                batch = self.decode(self.plane(idx), self.seq)
        else:
            batch = self.decode(self.plane(idx), self.seq)
        if self.keep_from is not None and self.fetched >= self.keep_from:
            self.kept[self.fetched] = {k: np.array(v) for k, v in batch.items()}
        self.fetched += 1
        return batch


def program_config(c: Dict[str, Any]):
    """The program's configuration for the file, refused where a size the
    file states is not what the program would run."""
    from repro.configs import get_config

    # "smoke": the program's own small variant of the architecture, which
    # the benchmark's tests run on the CPU
    cfg = get_config(c["arch"], smoke=bool(c.get("smoke"))).with_layers(
        c["num_hidden_layers"]).replace(
            norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"])
    ran = {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.kq_dim,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        "hidden_act": "silu" if cfg.activation == "swiglu" else cfg.activation,
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


class Session:
    """One trainer from set-up to the end of its window."""

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
        self.rows = data.token_rows(seed, n, seq, c["vocab_size"])
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
        self.feed = Feed(self.plane, seq, spec.trace)

        def init_state(cfg, rng, optimizer, compressor=None):
            params = weights.make_params(ref.layout(c), c, seed)
            return jax.jit(lambda p: {
                "params": p, "opt": optimizer.init(p),
                "step": jnp.zeros((), jnp.int32)})(params)

        # the program's own init draws leaf by leaf, eagerly; the weights
        # are the benchmark's, made in one jitted call
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

    def _epoch_done(self, epoch: int) -> None:
        if self.feed.stopped:
            raise WindowClosed(epoch)

    def _first_steps(self) -> Dict[str, Any]:
        """Drive the trainer through its first steps with its own call and
        feed, reading the optimizer's state after the first and the
        parameters after the last (copied to the host, so the device
        holds nothing of the benchmark's through the window)."""
        import jax

        b1 = self.trainer.optimizer.cfg.b1
        norms = jax.jit(lambda tree: ref.leaf_norms(
            jax.tree_util.tree_map(lambda m: m / (1 - b1), tree)))
        real = self.trainer.step_fn
        out: Dict[str, Any] = {}

        def probing(state, batch):
            new_state, metrics = real(state, batch)
            if "grad_norms" not in out:
                out["grad_norms"] = np.asarray(norms(new_state["opt"]["mu"]))
            return new_state, metrics

        self.trainer.step_fn = probing
        try:
            self.trainer.train()
        finally:
            self.trainer.step_fn = real
        out["params_after"] = jax.device_get(self.trainer.state["params"])
        out["losses"] = [h["loss"] for h in self.trainer.history]
        out["ids"] = [np.array(i) for i in self.feed.order]
        return out

    def window(self) -> Dict[str, Any]:
        spec, tr = self.spec, self.trainer
        tr.loop_cfg.max_steps = 0
        tr.loop_cfg.epochs = 1 << 30   # the deadline ends the window
        tr.start_epoch = 1
        st = tr.pipeline.stats
        before = {"steps": tr.global_step, "t_wait": st.t_wait}
        timed = None
        if spec.trace:
            real = tr.step_fn

            def traced_step(state, batch):
                with tracing.span("step"):
                    return real(state, batch)

            tr.step_fn = traced_step
            timed = tracing.TimedTrace(
                tracing.Tracer(spec.work_dir / "trace"),
                *tracing.trace_plan(spec.seconds))
        self.feed.keep_from = len(self.feed.order)  # every window batch
        compiles = spec.counter.programs
        setup_s = time.perf_counter() - spec.t_start
        t0 = time.perf_counter()
        self.feed.deadline = t0 + spec.seconds
        if timed is not None:
            timed.arm()
        try:
            tr.train()
        except WindowClosed:
            pass
        t1 = time.perf_counter()
        if timed is not None:
            timed.join()
        steps = tr.global_step - before["steps"]
        t = self.t
        counts = {
            "window_s": t1 - t0,
            "steps": steps,
            "tokens": steps * t["batch"] * t["seq_len"],
            "input_wait_s": st.t_wait - before["t_wait"],
            "flops_per_token": flops.train_flops_per_token(self.c, t["seq_len"]),
            "peak_flops": spec.peaks["bf16_flops_per_s"],
        }
        return {
            "setup_s": setup_s, "counts": counts,
            "window_compiles": spec.counter.programs - compiles,
            "memory": harness.device_info(spec.devices, spec.cell.chips),
            "trace": timed.tracer.reduce() if timed is not None and timed.taken
            else None,
        }

    def close(self) -> None:
        """Free the program's state and stop its threads."""
        self.plane.close()
        self.store.close()
        del self.trainer
        gc.collect()


def checks(spec, ev) -> List[harness.Check]:
    """Compare the checked steps with the reference, and every batch the
    window fetched with the records as generated."""
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


def reference_steps(spec, ev, nm=ref.F32, fault=None):
    """The reference's first steps over the batches the program took."""
    c, t = spec.cell.config, spec.cell.traffic
    batches = [(ev["rows"][i][:, :-1], ev["rows"][i][:, 1:])
               for i in ev["checked"]["ids"][:t["check_steps"]]]
    return ref.train_steps(
        lambda: weights.make_params(ref.layout(c), c, spec.seed), batches, c,
        ref.AdamW(lr=t["lr"], warmup_steps=t["warmup_steps"]), nm=nm,
        fault=fault)


def control(spec, ev) -> Dict[str, List[harness.Check]]:
    """Readings of the control (the reference in float8, the precision
    below the configuration's bfloat16, in the program's place) and of
    the fault of half the batch left out, planted in the reference."""
    lim = spec.cell.traffic["limits"]
    r = reference_steps(spec, ev)
    return {
        "control_fp8": compare(reference_steps(spec, ev, nm=ref.FP8), r, lim),
        "fault_half_batch": compare(
            reference_steps(spec, ev, fault="half_batch"), r, lim),
    }


def gaps(prog, r) -> Dict[str, float]:
    """Each step's loss, the first clipped gradient's norm by leaf and
    the norm of the parameters' change after the checked steps by leaf,
    as the worst gap against the reference: a leaf's gap over the larger
    of its reference norm and the median leaf's.  Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out of
    the change (Adam moves them by round-off alone)."""
    lp, lr = np.asarray(prog["losses"], float), np.asarray(r["losses"], float)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    gr, gp = r["grad_norms"], prog["grad_norms"]
    g_floor = max(float(np.median(gr)), 1e-30)
    grad_gap = float(np.max(np.abs(gp - gr) / np.maximum(gr, g_floor)))
    keep = gr >= 1e-3 * g_floor
    cr, cp = r["change_norms"][keep], prog["change_norms"][keep]
    c_floor = max(float(np.median(cr)), 1e-30)
    change_gap = float(np.max(np.abs(cp - cr) / np.maximum(cr, c_floor)))
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": change_gap}


def compare(prog, r, lim) -> List[harness.Check]:
    print(f"[check] losses {prog['losses']!r} reference {r['losses']!r}",
          flush=True)
    return [harness.Check(k, v, lim[k]) for k, v in gaps(prog, r).items()]


def change_norms(spec, after) -> np.ndarray:
    """Each leaf's norm of the change from the seed's weights to
    ``after``, on the device."""
    import jax

    c = spec.cell.config
    diff = jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
    return np.asarray(diff(jax.device_put(after),
                           weights.make_params(ref.layout(c), c, spec.seed)))


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
               "checked_losses": ev["checked"]["losses"]},
    )
