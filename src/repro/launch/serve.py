"""Serving launcher: offered-load driver over the continuous-batching
engine.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \
        --max-batch 4 --prompt-capacity 8 --gen 10 --requests 64 \
        --offered-load 0.6 --cache-mb 1

Generates a Poisson request stream at ``--offered-load`` requests per
engine step, drives :class:`~repro.serve.engine.ServeEngine`
(``--serve-mode continuous`` in-flight batching, or ``static``
run-to-completion batches for comparison), and reports p50/p99 latency
and TTFT in deterministic step-clock units plus wall-clock tokens/s.
With ``--cache-mb > 0`` each request's Zipf-popular feature ids are
served through the estimated-reuse :class:`RequestStreamCache`
(``--eviction-policy`` from the shared read-path flags), and the report
includes the measured hit rate beside the closed-form
:func:`~repro.storage.devices.served_hit_model` band.

The decode arena is sized once from ``--prompt-capacity + --gen`` at
engine construction — there is no ``extend_cache`` on this path.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax

from repro.data.synthetic import make_classification_dataset
from repro.launch.args import (
    add_model_args,
    add_read_path_args,
    model_config_from_args,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import (
    RequestStreamCache,
    ServeEngine,
    percentile,
    synthetic_workload,
)
from repro.storage.devices import served_hit_model, zipf_popularity
from repro.storage.record_store import RecordStore


def build_argparser():
    ap = argparse.ArgumentParser()
    add_read_path_args(ap)
    add_model_args(ap, default_arch="granite-3-8b")
    ap.add_argument("--serve-mode", default="continuous",
                    choices=["continuous", "static"],
                    help="continuous = in-flight batching (free slots "
                         "refill mid-decode); static = classic "
                         "run-to-completion batches")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="generation slots in the decode arena")
    ap.add_argument("--prompt-capacity", type=int, default=8,
                    help="prompt positions per slot (prompts right-pad "
                         "to this)")
    ap.add_argument("--gen", type=int, default=10,
                    help="generation positions per slot; the arena is "
                         "sized once from prompt-capacity + gen")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--offered-load", type=float, default=0.6,
                    help="mean request arrivals per engine step (Poisson)")
    ap.add_argument("--num-features", type=int, default=512,
                    help="feature-store records behind the request stream")
    ap.add_argument("--features-per-request", type=int, default=8)
    ap.add_argument("--zipf-alpha", type=float, default=1.1)
    ap.add_argument("--feature-data", default="",
                    help="existing fixed-size RecordStore to serve "
                         "features from (default: synthesize one)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    cfg = model_config_from_args(args)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))

    feature_cache = None
    store = None
    if args.cache_mb > 0:
        if args.feature_data:
            path = args.feature_data
        else:
            d = tempfile.mkdtemp(prefix="lirs_serve_")
            make_classification_dataset(
                f"{d}/features.rrec", args.num_features, dim=16,
                seed=args.seed,
            )
            path = f"{d}/features.rrec"
        store = RecordStore(path)
        feature_cache = RequestStreamCache(
            store,
            budget_bytes=int(args.cache_mb * 2**20),
            policy=args.eviction_policy,
        )

    requests = synthetic_workload(
        args.requests,
        vocab=cfg.vocab_size,
        offered_load=args.offered_load,
        prompt_len=(max(1, args.prompt_capacity // 2), args.prompt_capacity),
        gen_len=(max(1, args.gen // 2), args.gen),
        num_features=args.num_features if feature_cache is not None else 0,
        features_per_request=(
            args.features_per_request if feature_cache is not None else 0
        ),
        zipf_alpha=args.zipf_alpha,
        seed=args.seed,
    )

    engine = ServeEngine(
        cfg, params,
        max_batch=args.max_batch,
        prompt_capacity=args.prompt_capacity,
        max_new_tokens=args.gen,
        mode=args.serve_mode,
        feature_cache=feature_cache,
    )
    engine.warmup()
    tokens_before = engine.generated_tokens
    t0 = time.perf_counter()
    completions = engine.run(requests)
    wall = time.perf_counter() - t0
    tokens = engine.generated_tokens - tokens_before

    lat = [c.latency for c in completions]
    ttft = [c.ttft for c in completions]
    report = {
        "arch": cfg.name,
        "serve_mode": args.serve_mode,
        "max_batch": args.max_batch,
        "requests": len(completions),
        "offered_load": args.offered_load,
        "generated_tokens": tokens,
        "completion_tokens": sum(len(c.tokens) for c in completions),
        "slot_leaks": engine.max_batch - engine.free_slots,
        "decode_steps": engine.decode_steps,
        "tokens_per_step": round(tokens / max(engine.decode_steps, 1), 3),
        "tokens_per_s": round(tokens / max(wall, 1e-9), 1),
        "latency_p50_steps": round(percentile(lat, 50), 2),
        "latency_p99_steps": round(percentile(lat, 99), 2),
        "ttft_p50_steps": round(percentile(ttft, 50), 2),
        "ttft_p99_steps": round(percentile(ttft, 99), 2),
    }
    if feature_cache is not None:
        capacity = feature_cache.cache.capacity
        pop = zipf_popularity(args.num_features, args.zipf_alpha)
        report["feature_cache"] = {
            "policy": args.eviction_policy,
            "capacity_records": capacity,
            "hits": feature_cache.cache.hits,
            "misses": feature_cache.cache.misses,
            "hit_rate": round(feature_cache.hit_rate, 4),
            "model_lru": round(served_hit_model(pop, capacity, "lru"), 4),
            "model_clairvoyant": round(
                served_hit_model(pop, capacity, "belady"), 4
            ),
            "storage_cache_hits": store.stats.cache_hits,
            "storage_records_read": store.stats.batch_records,
        }
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    main()
