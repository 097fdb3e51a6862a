"""Serving engine + unified read-path API: the PR's contracts.

Engine invariants:
  * no slot leaks — after a mixed-length workload drains, every slot is
    free and every request completed, in both serve modes;
  * greedy decode in the shared arena is *identical* to a solo run of
    the same request (continuous batching changes scheduling, never
    tokens);
  * the decode arena is allocated exactly once — one
    ``serve/arena_alloc`` trace instant, no reallocation across
    prefills/decodes (there is no ``extend_cache`` on the serve path).

Estimated-reuse tier:
  * the request-stream cache serves byte-correct records and its
    hit/miss counters reconcile exactly with the store's ``IOStats``;
  * the measured Zipf hit rate lands in the closed-form
    ``served_hit_model`` band [LRU (Che), clairvoyant].

Read-path API redesign:
  * ``store_fetch_fn(**kwargs)`` (deprecated shim) and
    ``build_data_plane(store, ReadPathConfig(...))`` produce
    byte-identical batches across {dense, ragged} x {lru, belady};
  * the shared launcher flags round-trip into the same config;
  * ``ReadPathConfig.validate`` / ``build_data_plane`` reject the same
    invalid inputs the old keyword soup did.
"""
import argparse

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs.granite_3_8b import smoke_config
from repro.core import ReadPathConfig, batch_iter_fn_of, build_data_plane, close_data_plane
from repro.core.pipeline import store_fetch_fn
from repro.core.shuffler import LIRSShuffler
from repro.launch.args import (
    add_read_path_args,
    config_from_args,
    planner_from_args,
)
from repro.models import model as model_lib
from repro.obs import trace
from repro.prefetch import PrefetchingFetcher
from repro.serve import (
    EstimatedReusePolicy,
    Request,
    RequestStreamCache,
    ServeEngine,
    StepClock,
    percentile,
    synthetic_workload,
    zipf_probabilities,
)
from repro.storage.devices import served_hit_model, zipf_popularity
from repro.storage.record_store import RecordStore, RecordWriter

# ------------------------------------------------------------ fixtures
@pytest.fixture(scope="module")
def cfg():
    return smoke_config()


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fixed_store(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serve") / "fixed.rrec")
    rng = np.random.default_rng(7)
    recs = [rng.bytes(64) for _ in range(400)]
    with RecordWriter(path, record_size=64) as w:
        for r in recs:
            w.append(r)
    store = RecordStore(path)
    yield store, recs
    store.close()


@pytest.fixture(scope="module")
def variable_store(tmp_path_factory):
    from repro.core.location import LocationGenerator

    path = str(tmp_path_factory.mktemp("serve") / "var.rrec")
    rng = np.random.default_rng(8)
    recs = [rng.bytes(int(rng.integers(4, 80))) for _ in range(400)]
    with RecordWriter(path) as w:
        for r in recs:
            w.append(r)
    store = RecordStore(path)
    LocationGenerator().generate(store)
    yield store, recs
    store.close()


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prompt_capacity", 8)
    kw.setdefault("max_new_tokens", 6)
    return ServeEngine(cfg, params, **kw)


def _workload(cfg, n, load=0.8, seed=3):
    return synthetic_workload(
        n, vocab=cfg.vocab_size, offered_load=load,
        prompt_len=(2, 8), gen_len=(2, 6), seed=seed,
    )


# ------------------------------------------------- engine: slot hygiene
@pytest.mark.parametrize("mode", ["continuous", "static"])
def test_no_slot_leak_after_mixed_workload(cfg, params, mode):
    eng = _engine(cfg, params, mode=mode)
    reqs = _workload(cfg, 24)
    comps = eng.run(reqs)
    assert eng.free_slots == eng.max_batch
    assert eng.active == 0 and not eng.queue
    assert sorted(c.rid for c in comps) == sorted(r.rid for r in reqs)
    budget = {r.rid: r.max_new_tokens for r in reqs}
    for c in comps:
        assert len(c.tokens) == budget[c.rid]  # exact budget, no eos set
        assert c.arrival <= c.first_token <= c.finished


def _all_due_at_once(cfg, n):
    reqs = _workload(cfg, n, seed=5)
    for r in reqs:
        r.arrival = 0.0
    return reqs


def test_timeline_orders_arrival_admission_first_token_finish(cfg, params):
    """More requests than slots, all due at once: the first two take the
    slots at once, every later one waits in the queue, and each
    timeline runs arrival ≤ admitted ≤ first token ≤ finished."""
    eng = _engine(cfg, params, max_batch=2)
    comps = sorted(eng.run(_all_due_at_once(cfg, 6)), key=lambda c: c.rid)
    for c in comps:
        assert c.arrival <= c.admitted <= c.first_token <= c.finished
    assert [c.admitted - c.arrival > 0 for c in comps] == [False] * 2 + [True] * 4


class _TickClock(StepClock):
    """A clock that moves on at every reading, as a wall clock does."""

    def now(self) -> float:
        self.advance(1e-3)
        return super().now()


def test_first_token_is_stamped_after_its_prefill(cfg, params):
    eng = _engine(cfg, params, max_batch=2, clock=_TickClock())
    for c in eng.run(_workload(cfg, 5, seed=6)):
        assert c.arrival <= c.admitted < c.first_token <= c.finished


def test_slot_steps_count_the_occupied_slots_of_each_decode(cfg, params):
    """``slot_steps / decode_steps`` is the mean number of occupied slots
    a decode step carried, counted here by hand at every decode."""
    eng = _engine(cfg, params, max_batch=3)
    occupied = []
    real_decode = eng._decode

    def counting(*a):
        occupied.append(len(eng.slots))
        return real_decode(*a)

    eng._decode = counting
    eng.run(_workload(cfg, 10, load=1.5, seed=7))
    assert len(occupied) == eng.decode_steps > 0
    assert eng.slot_steps == sum(occupied)
    assert eng.slot_steps / eng.decode_steps == pytest.approx(np.mean(occupied))
    assert 1 < np.mean(occupied) <= eng.max_batch


def test_slots_reused_not_grown(cfg, params):
    """More requests than slots forces every slot through multiple
    admit/retire cycles; prefills count proves reuse, not growth."""
    eng = _engine(cfg, params, max_batch=2)
    reqs = _workload(cfg, 12, load=2.0)
    eng.run(reqs)
    assert eng.prefills == 12
    assert eng.free_slots == 2


# ------------------------------------ engine: scheduling changes nothing
@pytest.mark.parametrize("mode", ["continuous", "static"])
def test_greedy_tokens_identical_to_solo_run(cfg, params, mode):
    """The acceptance bar: per-request output under in-flight batching
    equals a solo run of that request — batching is pure scheduling."""
    reqs = _workload(cfg, 8, load=1.5, seed=11)
    eng = _engine(cfg, params, mode=mode)
    got = {c.rid: c.tokens for c in eng.run(reqs)}
    for r in reqs:
        solo = _engine(cfg, params, max_batch=1)
        [c] = solo.run([Request(rid=r.rid, prompt=r.prompt,
                                max_new_tokens=r.max_new_tokens)])
        assert got[r.rid] == c.tokens, f"rid {r.rid} diverged under {mode}"


def test_eos_retires_early_and_frees_slot(cfg, params):
    req = _workload(cfg, 1, seed=8)[0]
    req.arrival = 0.0
    base = _engine(cfg, params)
    [full] = base.run([req])
    # eos = the first decoded token that has not occurred earlier in the
    # output, so a greedy repeat of an earlier token cannot stop sooner
    j = next(
        i for i in range(1, len(full.tokens))
        if full.tokens[i] not in full.tokens[:i]
    )
    assert j < len(full.tokens) - 1  # eos lands before the budget
    eng = _engine(cfg, params, eos_id=full.tokens[j])
    [cut] = eng.run([Request(rid=0, prompt=req.prompt,
                             max_new_tokens=req.max_new_tokens)])
    assert cut.tokens == full.tokens[: j + 1]  # stops at first eos
    assert eng.free_slots == eng.max_batch


def test_continuous_retires_in_fewer_decode_steps(cfg, params):
    """The tentpole win, deterministically: free slots refilled
    mid-flight retire the same workload in fewer arena-wide steps."""
    reqs = _workload(cfg, 16, load=2.0, seed=9)
    cont = _engine(cfg, params, mode="continuous")
    stat = _engine(cfg, params, mode="static")
    cont.run(reqs)
    stat.run(list(reqs))
    assert cont.generated_tokens == stat.generated_tokens
    assert cont.decode_steps < stat.decode_steps


def test_submit_validates_against_arena(cfg, params):
    eng = _engine(cfg, params, prompt_capacity=4, max_new_tokens=3)
    with pytest.raises(ValueError, match="prompt_capacity"):
        eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                           max_new_tokens=2))
    with pytest.raises(ValueError, match="generation arena"):
        eng.submit(Request(rid=1, prompt=np.arange(2, dtype=np.int32),
                           max_new_tokens=9))
    with pytest.raises(ValueError, match="mode must be one of"):
        _engine(cfg, params, mode="batched")


def test_engine_refuses_unservable_block_kinds(cfg, params):
    bad = cfg.replace(stages=((("attn", "local_attn"), 1),))
    with pytest.raises(ValueError, match="local_attn"):
        ServeEngine(bad, params, max_batch=2, prompt_capacity=4,
                    max_new_tokens=2)


# ------------------------------------------- engine: one arena, forever
def test_arena_allocated_exactly_once(cfg, params):
    trace.disable()
    rec = trace.enable(capacity_per_thread=1024)
    try:
        eng = _engine(cfg, params)
        eng.run(_workload(cfg, 10, load=1.2, seed=2))
    finally:
        trace.disable()
    evs = rec.drain()
    allocs = [e for e in evs if e["name"] == "serve/arena_alloc"]
    assert len(allocs) == 1, "decode path must never reallocate the arena"
    assert allocs[0]["args"]["slots"] == eng.max_batch
    assert allocs[0]["args"]["capacity"] == eng.capacity
    prefills = [e for e in evs if e["name"] == "serve/prefill"]
    decodes = [e for e in evs if e["name"] == "serve/decode"]
    assert len(prefills) == eng.prefills == 10
    assert len(decodes) == eng.decode_steps > 0
    # every prefill/decode happens on the one already-allocated arena
    t0 = allocs[0]["ts"]
    assert all(e["ts"] >= t0 for e in prefills + decodes)


def test_arena_shapes_static_across_run(cfg, params):
    eng = _engine(cfg, params)
    before = [x.shape for x in jax.tree_util.tree_leaves(eng.arena)]
    eng.run(_workload(cfg, 6, seed=4))
    after = [x.shape for x in jax.tree_util.tree_leaves(eng.arena)]
    assert before == after


# ----------------------------------------------- estimated-reuse tier
def test_request_stream_cache_serves_correct_bytes(fixed_store):
    store, recs = fixed_store
    store.stats.reset()
    fc = RequestStreamCache(store, budget_bytes=50 * store.record_size)
    rng = np.random.default_rng(0)
    p = zipf_probabilities(store.num_records, 1.2)
    for step in range(120):
        ids = rng.choice(store.num_records, size=8, p=p).astype(np.int64)
        out, hit = fc.fetch(ids, float(step))
        assert out.shape == (8, store.record_size)
        for row, i in zip(out, ids):
            assert bytes(row) == recs[i]
    assert 0.0 < fc.hit_rate < 1.0


def test_cache_counters_reconcile_with_iostats(fixed_store):
    """The ISSUE's reconciliation bar: the cache's hits/misses and the
    store's IOStats tell one consistent story."""
    store, _ = fixed_store
    store.stats.reset()
    fc = RequestStreamCache(store, budget_bytes=40 * store.record_size)
    rng = np.random.default_rng(1)
    p = zipf_probabilities(store.num_records, 1.1)
    for step in range(150):
        ids = rng.choice(store.num_records, size=6, p=p).astype(np.int64)
        fc.fetch(ids, float(step))
    assert store.stats.cache_hits == fc.cache.hits
    assert store.stats.batch_records == fc.cache.misses
    assert fc.cache.hits + fc.cache.misses == fc.fetched == 150 * 6
    assert fc.cache.used_bytes <= fc.cache.budget_bytes


def test_hit_rate_lands_in_served_hit_model_band(fixed_store):
    store, _ = fixed_store
    store.stats.reset()
    n, alpha, cap_records = store.num_records, 1.2, 48
    fc = RequestStreamCache(
        store, budget_bytes=cap_records * store.record_size, policy="belady"
    )
    rng = np.random.default_rng(7)
    p = zipf_probabilities(n, alpha)
    for step in range(400):
        ids = rng.choice(n, size=8, p=p).astype(np.int64)
        fc.fetch(ids, float(step))
    pop = zipf_popularity(n, alpha)
    lo = served_hit_model(pop, fc.cache.capacity, "lru")
    hi = served_hit_model(pop, fc.cache.capacity, "belady")
    assert lo < hi
    # cold-start slack: the closed forms are steady-state
    assert lo - 0.07 <= fc.hit_rate <= hi + 0.07


def test_request_stream_cache_rejects_variable_store(variable_store):
    store, _ = variable_store
    with pytest.raises(ValueError, match="fixed-size"):
        RequestStreamCache(store, budget_bytes=4096)


def test_estimated_reuse_policy_learns_interarrival_gaps():
    pol = EstimatedReusePolicy(16, ewma=0.5, cold_gap=100.0)
    one = np.array([3], np.int64)
    # cold id: estimated far in the future
    assert pol.estimate_next_use(one, 0.0)[0] == 100
    for t in (0.0, 10.0, 20.0, 30.0, 40.0):
        pol.observe(one, t)
    est = pol.estimate_next_use(one, 40.0)[0]
    # EWMA converged toward the true period of 10
    assert 40 + 10 <= est <= 40 + 50
    # an id never observed still looks cold
    assert pol.estimate_next_use(np.array([9], np.int64), 40.0)[0] == 140
    with pytest.raises(ValueError, match="ewma"):
        EstimatedReusePolicy(4, ewma=0.0)


def test_served_hit_model_shape_and_edges():
    pop = zipf_popularity(100, 1.1)
    assert served_hit_model(pop, 0, "lru") == 0.0
    assert served_hit_model(pop, 100, "lru") == 1.0
    assert served_hit_model(pop, 150, "belady") == 1.0
    prev_lru = prev_bel = 0.0
    for cap in (5, 20, 50, 80):
        lru = served_hit_model(pop, cap, "lru")
        bel = served_hit_model(pop, cap, "belady")
        assert lru <= bel + 1e-12  # clairvoyant dominates Che-LRU
        assert lru >= prev_lru and bel >= prev_bel  # monotone in capacity
        prev_lru, prev_bel = lru, bel
    with pytest.raises(ValueError):
        served_hit_model(pop, 10, "fifo")


# ------------------------------------------- read-path API: byte identity
def _drain_bytes(fetch_fn, batches):
    out = []
    for idx in batches:
        item = fetch_fn(idx)
        if isinstance(item, np.ndarray):
            out.append(bytes(item.reshape(-1)))
        else:  # RaggedBatch
            out.append(bytes(item.arena) + item.offsets.tobytes()
                       + item.lengths.tobytes())
    return out


@pytest.mark.parametrize("policy", ["lru", "belady"])
@pytest.mark.parametrize("kind", ["dense", "ragged"])
def test_shim_and_data_plane_byte_identical(
    fixed_store, variable_store, kind, policy
):
    """The migration's no-behavior-change proof: the deprecated
    ``store_fetch_fn`` kwargs and the equivalent ``ReadPathConfig``
    produce byte-identical batches on the tiered path, across the
    {dense, ragged} x {lru, belady} matrix."""
    store, _ = fixed_store if kind == "dense" else variable_store
    budget = int(store.file_size * 0.3)
    kw = dict(shuffler=LIRSShuffler(store.num_records, 32, seed=5),
              cache_budget_bytes=budget, lookahead=4, workers=2,
              eviction_policy=policy, max_epochs=2)

    def epochs(f):
        return [b for e in range(2)
                for b in _drain_bytes(f, f.batch_iter(e))]

    with pytest.warns(DeprecationWarning, match="build_data_plane"):
        old = store_fetch_fn(store, **kw)
    assert isinstance(old, PrefetchingFetcher)
    with old:
        old_bytes = epochs(old)
        assert old.last_error is None
    new = build_data_plane(store, ReadPathConfig(**kw))
    with new:
        new_bytes = epochs(new)
        assert new.last_error is None
    assert old_bytes == new_bytes


@pytest.mark.parametrize("kind", ["dense", "ragged"])
def test_shim_byte_identical_on_direct_path(fixed_store, variable_store, kind):
    store, _ = fixed_store if kind == "dense" else variable_store
    rng = np.random.default_rng(2)
    batches = [rng.choice(store.num_records, size=16, replace=False)
               .astype(np.int64) for _ in range(6)]
    with pytest.warns(DeprecationWarning):
        old = store_fetch_fn(store, workers=2)
    new = build_data_plane(store, ReadPathConfig(workers=2))
    assert _drain_bytes(old, batches) == _drain_bytes(new, batches)
    # direct planes have no batch_iter / background resources
    assert batch_iter_fn_of(new) is None
    close_data_plane(new)  # no-op, must not raise


def test_data_plane_helpers_on_tiered_path(fixed_store):
    store, _ = fixed_store
    sh = LIRSShuffler(store.num_records, 32, seed=1)
    plane = build_data_plane(store, ReadPathConfig(
        shuffler=sh, cache_budget_bytes=int(store.file_size * 0.2),
        max_epochs=1,
    ))
    assert batch_iter_fn_of(plane) == plane.batch_iter
    close_data_plane(plane)


# --------------------------------------------- read-path API: validation
def test_read_path_config_validation():
    with pytest.raises(ValueError, match="auto"):
        ReadPathConfig(mode="sparse").validate()
    with pytest.raises(ValueError, match="eviction policy"):
        ReadPathConfig(eviction_policy="mru").validate()
    with pytest.raises(ValueError, match="shuffler="):
        ReadPathConfig(cache_budget_bytes=1024).validate()
    cfg = ReadPathConfig().validate()
    assert not cfg.tiered
    assert cfg.replace(cache_budget_bytes=1, shuffler=object()).tiered


def test_build_data_plane_mode_errors(fixed_store, variable_store):
    fstore, _ = fixed_store
    vstore, _ = variable_store
    with pytest.raises(ValueError, match="dense mode"):
        build_data_plane(vstore, ReadPathConfig(mode="dense"))
    with pytest.raises(TypeError, match="BatchBufferRing"):
        build_data_plane(fstore, ReadPathConfig(mode="dense", ring=object()))
    with pytest.raises(TypeError, match="RaggedBufferRing"):
        build_data_plane(vstore, ReadPathConfig(mode="ragged", ring=object()))


# ----------------------------------------------- shared launcher flags
def test_launcher_flags_round_trip_into_config():
    ap = argparse.ArgumentParser()
    add_read_path_args(ap)
    args = ap.parse_args([
        "--cache-mb", "2", "--eviction-policy", "lru",
        "--prefetch-planner", "off", "--io-workers", "3",
        "--prefetch-lookahead", "5",
    ])
    sentinel = object()
    cfg = config_from_args(args, shuffler=sentinel, max_epochs=4)
    assert cfg.cache_budget_bytes == 2 * 2**20
    assert cfg.eviction_policy == "lru"
    assert cfg.prefetch_planner is False
    assert cfg.workers == 3 and cfg.lookahead == 5
    assert cfg.shuffler is sentinel and cfg.max_epochs == 4
    assert cfg.tiered


def test_planner_tri_state_mapping():
    ap = add_read_path_args(argparse.ArgumentParser())
    for flag, want in (("auto", None), ("on", True), ("off", False)):
        args = ap.parse_args(["--prefetch-planner", flag])
        assert planner_from_args(args) is want


def test_defaults_parse_to_untiered_config():
    ap = add_read_path_args(argparse.ArgumentParser())
    cfg = config_from_args(ap.parse_args([]))
    assert not cfg.tiered
    assert cfg.eviction_policy == "belady"


# ------------------------------------------------------------ utilities
def test_percentile_nearest_rank():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 50) == 2.0
    assert percentile(xs, 99) == 4.0
    assert percentile([], 50) == 0.0


def test_step_clock_and_workload_determinism(cfg):
    c = StepClock()
    c.advance(2.5)
    assert c.now() == 2.5
    a = _workload(cfg, 10, seed=42)
    b = _workload(cfg, 10, seed=42)
    assert all(x.arrival == y.arrival and np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))
    assert all(a[i].arrival <= a[i + 1].arrival for i in range(9))
