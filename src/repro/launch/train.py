"""Production-shaped training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --smoke \
        --steps 200 --shuffler lirs --ckpt-dir /tmp/ck

Wires: synthetic token corpus in a RecordStore → shuffle strategy (LIRS /
BMF / TFIP / CorgiPile / Corgi²) →
prefetching pipeline → jitted train step → checkpoints + Eq. 1 report.
The step is one unsharded program on the default device (one chip, or
the CPU); ``--hosts N`` multiplies the I/O plane, not the compute.
``--layers N`` cuts a published configuration's depth to fit one chip:

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --layers 1 --seq-len 4096 --batch 1 --num-records 16 --steps 32
"""
from __future__ import annotations

import argparse
import json
import tempfile

from repro.core.readpath import build_data_plane
from repro.data.synthetic import decode_token_batch, make_token_dataset
from repro.launch.args import (
    add_model_args,
    add_read_path_args,
    config_from_args,
    make_shuffler_from_args,
    model_config_from_args,
    planner_from_args,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.storage.faults import FaultInjector, FaultSpec
from repro.storage.record_store import IOStats, RecordStore
from repro.train.loop import Trainer, TrainLoopConfig
from repro.train.optimizer import AdamWConfig


def build_argparser():
    ap = argparse.ArgumentParser()
    add_read_path_args(ap)
    add_model_args(ap, default_arch="minitron-8b")
    ap.add_argument("--num-records", type=int, default=512)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--steps", type=int, default=0, help="cap total steps")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data", default="", help="existing RecordStore path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at-step", type=int, default=-1)
    ap.add_argument("--io-producers", type=int, default=1,
                    help="pipeline producer threads (ordered reassembly)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="run the data plane as an N-host clairvoyant "
                         "cluster (repro.prefetch.distributed): each host "
                         "owns a slice of every global batch, caches what "
                         "it consumes, and serves peers host-to-host "
                         "before storage.  Batches stay byte-identical to "
                         "--hosts 1; compute is unchanged (single device). "
                         "Needs --cache-mb > 0 (with --hosts > 1 the "
                         "budget is the FLEET budget, split evenly)")
    ap.add_argument("--chaos", default="",
                    help="fault-injection spec for the read path, e.g. "
                         "'seed=1,transient=0.05,stall=0.01,stall_s=0.2' "
                         "(see repro.storage.faults.FaultSpec.parse); "
                         "empty = no injection")
    ap.add_argument("--verify-checksums", default="auto",
                    choices=["auto", "full", "off"],
                    help="RREC v2 payload verification: auto (only "
                         "retried/hedged extents — free on the clean "
                         "path), full (every record), off")
    ap.add_argument("--trace", default="",
                    help="record spans across the whole I/O stack "
                         "(storage/cache/remote/pipeline/train) and write "
                         "a Chrome trace-event JSON here at exit — open "
                         "it in Perfetto (ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default="",
                    help="dump the metrics-registry snapshot (counters, "
                         "gauges, latency histograms) as JSON here at exit")
    ap.add_argument("--drift-device", default="",
                    choices=["", "hdd", "ssd", "optane"],
                    help="also price measured vs modeled storage reads "
                         "through this Table 2 device model in the drift "
                         "report (needs --cache-mb > 0, --hosts 1)")
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    if args.trace:
        obs_trace.enable()
    registry = obs_metrics.reset_registry()
    cfg = model_config_from_args(args)

    injector = (
        FaultInjector(FaultSpec.parse(args.chaos)) if args.chaos else None
    )
    if args.data:
        path = args.data
    else:
        d = tempfile.mkdtemp(prefix="lirs_data_")
        meta = make_token_dataset(
            f"{d}/corpus.rrec", args.num_records, args.seq_len,
            cfg.vocab_size,
            seed=args.seed,
        )
        path = meta.path
    store = RecordStore(
        path, fault_injector=injector, verify=args.verify_checksums
    )
    seq = args.seq_len

    shuffler = make_shuffler_from_args(args, store, args.batch, args.seed)

    fetcher = None
    cluster = None
    batch_iter_fn = None
    if args.cache_mb > 0 and args.hosts > 1:
        # distributed clairvoyant data plane: H in-process hosts, each
        # with its own store handle, shard view, and cache; misses route
        # to the predicted holding peer before storage.  Compute stays on
        # this device — only the I/O plane is multi-host.
        from repro.prefetch.distributed import ClusterFetcher, make_cluster

        cluster = make_cluster(
            lambda: RecordStore(
                path, fault_injector=injector, verify=args.verify_checksums
            ),
            shuffler,
            args.hosts,
            budget_bytes=int(args.cache_mb * 2**20),
            lookahead=args.prefetch_lookahead,
            workers=args.io_workers,
            background=True,
            max_epochs=args.epochs,
            policy=args.eviction_policy,
            planner=planner_from_args(args),
        )
        fetcher = ClusterFetcher(cluster)
        batch_iter_fn = fetcher.batch_iter

        if store.variable:
            def fetch(idx):
                return decode_token_batch(fetcher(idx).tolist(), seq)
        else:
            def fetch(idx):
                return decode_token_batch(fetcher(idx), seq)
    elif args.cache_mb > 0:
        # tiered read path: DRAM cache + clairvoyant prefetch along the
        # shuffler's known index stream (batch bytes unchanged).
        # max_epochs stops the lookahead from prefetching past the last
        # epoch (reads nobody would consume, stalling shutdown)
        fetcher = build_data_plane(
            store,
            config_from_args(args, shuffler=shuffler, max_epochs=args.epochs),
        )
        batch_iter_fn = fetcher.batch_iter

        if store.variable:
            def fetch(idx):
                return decode_token_batch(fetcher(idx).tolist(), seq)
        else:
            def fetch(idx):
                return decode_token_batch(fetcher(idx), seq)
    elif store.variable:
        def fetch(idx):
            return decode_token_batch(
                store.read_batch_coalesced(idx, workers=args.io_workers), seq
            )
    else:
        # coalesced multi-queue hot path: dense buffer, zero-copy decode
        def fetch(idx):
            return decode_token_batch(
                store.read_batch_into(idx, workers=args.io_workers), seq
            )

    # per-epoch counter snapshots for the drift report: cumulative at each
    # epoch end, so adjacent deltas give per-epoch (steady-state) windows
    epoch_snaps: list = []
    if cluster is not None:
        def epoch_hook(epoch):
            epoch_snaps.append(cluster.aggregate_io())
    else:
        def epoch_hook(epoch):
            epoch_snaps.append(store.stats.snapshot())

    trainer = Trainer(
        cfg,
        fetch,
        shuffler,
        TrainLoopConfig(
            epochs=args.epochs, max_steps=args.steps, ckpt_dir=args.ckpt_dir,
            fail_at_step=args.fail_at_step, seed=args.seed,
        ),
        opt_cfg=AdamWConfig(lr=args.lr, warmup_steps=10),
        num_producers=args.io_producers,
        batch_iter_fn=batch_iter_fn,
        epoch_hook=epoch_hook,
    )

    obs_metrics.bind_store(registry, store)
    obs_metrics.bind_pipeline(registry, trainer.pipeline)
    if cluster is not None:
        obs_metrics.bind_cluster(registry, cluster)
    elif fetcher is not None:
        obs_metrics.bind_fetcher(registry, fetcher)
    if injector is not None:
        obs_metrics.bind_fault_log(registry, injector.log)
    if args.resume and trainer.try_resume():
        print(f"resumed at step {trainer.global_step}")
    summary = trainer.train()
    if cluster is not None:
        agg = cluster.aggregate_io()
        fetcher.close()
        summary["distributed"] = {
            "hosts": cluster.num_hosts,
            "policy": args.eviction_policy,
            "fleet_capacity_records": cluster.placement.aggregate_capacity(),
            "expected_steady_storage_records_per_epoch": (
                cluster.placement.expected_storage_reads()
            ),
            **agg,
        }
    elif fetcher is not None:
        fetcher.close()
        summary["cache"] = {
            "policy": fetcher.cache.policy,
            "planner": fetcher.planner,
            "budget_bytes": fetcher.cache.budget_bytes,
            "used_bytes": fetcher.cache.used_bytes,
            "demand_hits": fetcher.cache.hits,
            "demand_misses": fetcher.cache.misses,
            "window_hits": fetcher.scheduler.window_hits,
            "prefetched_records": fetcher.prefetch_records,
            "rejected_inserts": fetcher.cache.rejected,
            "planned_skips": fetcher.cache.planned_skips,
            "doomed_records": fetcher.scheduler.doomed_records,
            "probe_skips": fetcher.probe_skips,
            "stray_unpins": fetcher.cache.stray_unpins,
            "scratch_copies": fetcher.cache.scratch_copies,
            "invalidations": fetcher.cache.invalidations,
            "plans_failed": fetcher.plans_failed,
            "worker_restarts": fetcher.worker_restarts,
        }
    st = store.stats
    summary["io_resilience"] = {
        "verify": store.verify,
        "rrec_version": store.version,
        "retries": st.retries,
        "hedged_reads": st.hedged_reads,
        "checksum_failures": st.checksum_failures,
        "degraded_batches": st.degraded_batches,
    }
    if injector is not None:
        summary["io_resilience"]["injected"] = injector.counters()

    # model-vs-measured drift over the steady (warm) epochs: the cold
    # first epoch is all misses by construction, so it only anchors the
    # delta window
    if len(epoch_snaps) >= 2 and (cluster is not None or fetcher is not None):
        from repro.obs import drift

        n = store.num_records
        steady_epochs = len(epoch_snaps) - 1
        window_frac = min(1.0, args.prefetch_lookahead * args.batch / n)
        first, last = epoch_snaps[0], epoch_snaps[-1]
        if cluster is not None:
            d = {k: last[k] - first[k] for k in last}
            report = drift.distributed_report(
                n_records=n,
                hosts=args.hosts,
                capacity_frac_global=min(
                    1.0, cluster.placement.aggregate_capacity() / n
                ),
                policy=args.eviction_policy,
                window_frac=window_frac,
                epochs=steady_epochs,
                remote_hits=d["remote_hits"],
                storage_records=d["storage_records"],
                local_hits=d["local_hits"],
            )
        else:
            d = IOStats.delta(last, first)
            report = drift.single_host_report(
                n_records=n,
                record_bytes=store.record_size or 0,
                capacity_frac=min(1.0, fetcher.cache.capacity / n),
                policy=args.eviction_policy,
                planner_on=bool(fetcher.planner),
                window_frac=window_frac,
                batch_frac=min(1.0, args.batch / n),
                epochs=steady_epochs,
                storage_records=d["batch_records"],
                storage_ios=d["batch_ios"],
                storage_bytes=d["bytes_read"],
                device=args.drift_device or None,
            )
        summary["drift"] = report.to_dict()

    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            f.write(registry.to_json(indent=1))
        summary["metrics_json"] = args.metrics_json
    if args.trace:
        rec = obs_trace.get_recorder()
        if rec is not None:
            doc = rec.export_chrome(args.trace)
            summary["trace"] = {
                "path": args.trace,
                "events": len(doc["traceEvents"]),
            }
        obs_trace.disable()
    print(json.dumps(summary, indent=1))
    return summary


if __name__ == "__main__":
    main()
