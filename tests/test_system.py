"""End-to-end system behaviour: training over a real record store with the
LIRS pipeline, fault-tolerant resume, checkpoint integrity, optimizer."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # heavy; excluded from tier-1 (see pytest.ini)

import jax.numpy as jnp

from repro.configs import get_config
from repro.data.synthetic import decode_token_batch, make_token_dataset
from repro.storage.record_store import RecordStore
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import PreemptionError, Trainer, TrainLoopConfig, make_shuffler
from repro.train.optimizer import AdamW, AdamWConfig


@pytest.fixture(scope="module")
def token_store(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    meta = make_token_dataset(str(d / "tok.rrec"), 64, seq_len=16, vocab=64, seed=2)
    store = RecordStore(meta.path)
    return store, meta


def _trainer(store, *, fail_at=-1, ckpt_dir="", shuffler="lirs", epochs=3):
    cfg = get_config("minitron-8b", smoke=True).replace(vocab_size=64)

    def fetch(idx):
        return decode_token_batch(store.read_batch(idx), 16)

    return Trainer(
        cfg,
        fetch,
        make_shuffler(shuffler, 64, 8, seed=0),
        TrainLoopConfig(
            epochs=epochs, ckpt_every=4, ckpt_dir=ckpt_dir,
            fail_at_step=fail_at, seed=0,
        ),
        opt_cfg=AdamWConfig(lr=3e-3, warmup_steps=2),
    )


def test_training_reduces_loss(token_store):
    store, _ = token_store
    t = _trainer(store)
    summary = t.train()
    assert summary["steps"] == 24
    losses = [h["loss"] for h in t.history]
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.2
    assert all(np.isfinite(l) for l in losses)
    # Eq.1 accounting is live
    assert summary["t_comp"] > 0 and summary["t_load"] > 0
    # the summary carries what a chip run checks: every loss, step wall
    # times, and where the train state lives
    assert summary["losses"] == losses
    assert summary["first_step_s"] > 0 and summary["median_step_s"] > 0
    assert summary["state_platforms"] == ["cpu"]


def test_preemption_resume_completes(token_store, tmp_path):
    store, _ = token_store
    t = _trainer(store, fail_at=10, ckpt_dir=str(tmp_path / "ck"))
    with pytest.raises(PreemptionError):
        t.train()
    t2 = _trainer(store, ckpt_dir=str(tmp_path / "ck"))
    assert t2.try_resume()
    assert t2.global_step == 10
    summary = t2.train()
    assert summary["steps"] == 24  # exactly 3 epochs x 8 steps total


def test_resume_is_deterministic(token_store, tmp_path):
    """Uninterrupted run == preempted+resumed run (same final loss)."""
    store, _ = token_store
    base = _trainer(store, epochs=2)
    base.train()
    ref_loss = base.history[-1]["loss"]

    t1 = _trainer(store, fail_at=9, ckpt_dir=str(tmp_path / "ck2"), epochs=2)
    with pytest.raises(PreemptionError):
        t1.train()
    t2 = _trainer(store, ckpt_dir=str(tmp_path / "ck2"), epochs=2)
    t2.try_resume()
    t2.train()
    # resume replays from step 8 (last checkpoint at ckpt_every=4 boundary)
    np.testing.assert_allclose(t2.history[-1]["loss"], ref_loss, rtol=1e-4)


def test_bmf_and_tfip_pipelines_also_train(token_store):
    store, _ = token_store
    for kind in ("bmf", "tfip"):
        t = _trainer(store, shuffler=kind, epochs=1)
        s = t.train()
        assert s["steps"] == 8
        assert np.isfinite(s["final_loss"])


def test_checkpoint_manager_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    state = {"a": jnp.arange(10, dtype=jnp.float32), "b": {"c": jnp.ones((3, 3))}}
    for step in (5, 10, 15):
        cm.save(step, state)
    assert cm.latest_step() == 15
    # keep=2: oldest garbage-collected
    assert len(cm._valid_checkpoints()) == 2
    got, extra, step = cm.restore(state)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(10, dtype=np.float32))


def test_checkpoint_ignores_partial(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    state = {"x": jnp.zeros(4)}
    cm.save(7, state)
    # a torn checkpoint: directory without manifest
    bad = tmp_path / "step_0000000009"
    bad.mkdir()
    (bad / "arrays.npz").write_bytes(b"garbage")
    assert cm.latest_step() == 7


def test_adamw_reduces_quadratic():
    opt = AdamW(AdamWConfig(lr=0.1, warmup_steps=1, weight_decay=0.0))
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_master_weights_bf16():
    opt = AdamW(AdamWConfig(lr=0.05, warmup_steps=1, weight_decay=0.0))
    params = {"w": jnp.asarray([1.0, -1.0], jnp.bfloat16)}
    state = opt.init(params)
    assert "master" in state and state["master"]["w"].dtype == jnp.float32
    for _ in range(50):
        grads = {"w": 2 * state["master"]["w"].astype(jnp.bfloat16)}
        params, state, _ = opt.update(grads, state, params)
    assert params["w"].dtype == jnp.bfloat16
    assert float(jnp.abs(state["master"]["w"]).max()) < 0.2
