"""Fault-tolerant training loop wiring model + optimizer + LIRS pipeline.

Features exercised by examples/tests:
  * LIRS / BMF / TFIP batch composition over a real RecordStore
  * background prefetch with Eq. 1 accounting (T_load/T_comp/T_overlap)
  * periodic atomic checkpoints + exact resume (model, optimizer, sampler)
  * simulated preemption (``fail_at_step``) for fault-tolerance tests
  * metrics JSONL log
"""
from __future__ import annotations

import dataclasses
import json
import statistics
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.core.pipeline import InputPipeline
from repro.core.shuffler import (
    BMFShuffler,
    CorgiPileShuffler,
    CorgiSquaredShuffler,
    LIRSShuffler,
    TFIPShuffler,
)
from repro.obs import trace as _trace
from repro.models.config import ModelConfig
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import AdamW, AdamWConfig
from repro.train.steps import init_train_state, make_train_step


class PreemptionError(RuntimeError):
    pass


@dataclasses.dataclass
class TrainLoopConfig:
    epochs: int = 1
    max_steps: int = 0  # 0 = no cap
    ckpt_every: int = 50
    ckpt_dir: str = ""
    keep_ckpts: int = 2
    log_path: str = ""
    fail_at_step: int = -1  # simulate preemption (tests)
    seed: int = 0


def make_shuffler(kind: str, num_items: int, batch_size: int, seed: int = 0, **kw):
    if kind == "lirs":
        return LIRSShuffler(num_items, batch_size, seed=seed, **kw)
    if kind == "lirs_page":
        return LIRSShuffler(num_items, batch_size, seed=seed, page_aware=True, **kw)
    if kind == "bmf":
        nb = max(1, num_items // batch_size)
        return BMFShuffler(num_items, nb, seed=seed)
    if kind == "tfip":
        return TFIPShuffler(num_items, batch_size, kw.pop("queue_size", 16), seed=seed)
    if kind in ("corgipile", "corgi2"):
        cls = CorgiPileShuffler if kind == "corgipile" else CorgiSquaredShuffler
        return cls(
            num_items,
            batch_size,
            kw.pop("block_records", max(1, batch_size // 2)),
            buffer_blocks=kw.pop("buffer_blocks", 2),
            seed=seed,
            **kw,
        )
    raise ValueError(kind)


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        fetch_fn: Callable[[np.ndarray], Dict[str, np.ndarray]],
        shuffler,
        loop_cfg: TrainLoopConfig,
        opt_cfg: AdamWConfig = AdamWConfig(),
        put_fn: Optional[Callable] = None,
        num_producers: int = 1,
        recycle_fn: Optional[Callable] = None,
        batch_iter_fn: Optional[Callable] = None,
        epoch_hook: Optional[Callable[[int], None]] = None,
    ):
        """``batch_iter_fn`` overrides the default ``shuffler.epoch_batches``
        source — e.g. a ``PrefetchingFetcher.batch_iter``, which re-syncs
        the clairvoyant lookahead window at each epoch boundary while
        yielding the identical batch sequence.  ``epoch_hook(epoch)`` fires
        after each completed epoch — the observability layer uses it to
        snapshot per-epoch I/O counters for drift detection."""
        self.cfg = cfg
        self.loop_cfg = loop_cfg
        self.optimizer = AdamW(opt_cfg)
        self.shuffler = shuffler
        self.pipeline = InputPipeline(
            batch_iter_fn=batch_iter_fn
            or (lambda epoch: shuffler.epoch_batches(epoch)),
            fetch_fn=fetch_fn,
            put_fn=put_fn,
            num_producers=num_producers,
            recycle_fn=recycle_fn,
        )
        self.step_fn = jax.jit(
            make_train_step(cfg, self.optimizer), donate_argnums=(0,)
        )
        self.state = init_train_state(cfg, jax.random.PRNGKey(loop_cfg.seed), self.optimizer)
        self.global_step = 0
        self.start_epoch = 0
        self.start_step_in_epoch = 0
        self.ckpt = (
            CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.keep_ckpts)
            if loop_cfg.ckpt_dir
            else None
        )
        self.epoch_hook = epoch_hook
        self.history: list = []
        self._log_f = open(loop_cfg.log_path, "a") if loop_cfg.log_path else None

    # ------------------------------------------------------------ resume
    def try_resume(self) -> bool:
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        self.state, extra, step = self.ckpt.restore(self.state)
        self.state = jax.tree_util.tree_map(jax.numpy.asarray, self.state)
        self.global_step = step
        self.start_epoch = extra.get("epoch", 0)
        self.start_step_in_epoch = extra.get("step_in_epoch", 0)
        return True

    # ------------------------------------------------------------- train
    def train(self) -> Dict[str, Any]:
        lc = self.loop_cfg
        step_in_epoch = 0
        try:
            for epoch in range(self.start_epoch, lc.epochs):
                skip = self.start_step_in_epoch if epoch == self.start_epoch else 0
                step_in_epoch = 0
                for batch in self.pipeline.epoch(epoch):
                    if step_in_epoch < skip:  # replaying a resumed epoch
                        step_in_epoch += 1
                        continue
                    if lc.fail_at_step >= 0 and self.global_step == lc.fail_at_step:
                        raise PreemptionError(f"simulated preemption @ {self.global_step}")
                    t0 = time.perf_counter()
                    # dispatch through _log's float() sync: the step's
                    # device work ends inside this span
                    with _trace.span(
                        "train/step",
                        "train",
                        args={"step": self.global_step, "epoch": epoch}
                        if _trace.enabled()
                        else None,
                    ):
                        with _trace.span("train/dispatch", "train"):
                            self.state, metrics = self.step_fn(self.state, batch)
                        self.global_step += 1
                        step_in_epoch += 1
                        self._log(epoch, metrics, t0)
                    if self.ckpt and self.global_step % lc.ckpt_every == 0:
                        self._save(epoch, step_in_epoch)
                    if lc.max_steps and self.global_step >= lc.max_steps:
                        return self.summary()
                if self.epoch_hook is not None:
                    self.epoch_hook(epoch)
                if self.ckpt:
                    self._save(epoch + 1, 0)
        except (KeyboardInterrupt, PreemptionError):
            # preemption path: persist everything needed for exact resume
            if self.ckpt:
                self._save(epoch, step_in_epoch)
            raise
        finally:
            if self._log_f:
                self._log_f.close()
                self._log_f = None
        return self.summary()

    def _save(self, epoch: int, step_in_epoch: int = 0):
        self.ckpt.save(
            self.global_step,
            self.state,
            extra={"epoch": epoch, "step_in_epoch": step_in_epoch},
        )

    def _log(self, epoch: int, metrics: Dict, t0: float):
        with _trace.span("train/sync", "train"):
            rec = {
                "step": self.global_step,
                "epoch": epoch,
                **{k: float(v) for k, v in metrics.items()},
            }
        # float() above waited for the step: wall time of the dispatched
        # step, compile included on the first
        rec["step_s"] = time.perf_counter() - t0
        self.history.append(rec)
        if self._log_f:
            self._log_f.write(json.dumps(rec) + "\n")

    def summary(self) -> Dict[str, Any]:
        s = self.pipeline.stats
        step_s = [h["step_s"] for h in self.history]
        return {
            "steps": self.global_step,
            "final_loss": self.history[-1]["loss"] if self.history else None,
            "losses": [h["loss"] for h in self.history],
            "first_step_s": step_s[0] if step_s else None,
            "median_step_s": (
                statistics.median(step_s[1:]) if len(step_s) > 1 else None
            ),
            "state_platforms": sorted({
                d.platform
                for x in jax.tree_util.tree_leaves(self.state)
                for d in x.devices()
            }),
            "t_load": s.t_load,
            "t_comp": s.t_comp,
            "t_overlap": s.t_overlap,
            "t_unhidden_load": s.t_wait,
            "effective_time": s.effective_epoch_time(),
        }
