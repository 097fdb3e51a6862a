"""One-chip smoke run of the system's main paths on a TPU.

    python chip_smoke.py [--seed N]

Runs in one process (a chip belongs to one process at a time), through
the entry points a user calls, and checks what comes out:

  train  repro.launch.train.main: granite-3-8b at its published widths
         cut to 1 layer, seq 4096, batch 1, LIRS shuffling, a Belady
         DRAM tier of a quarter of the corpus, two epochs.  Every loss
         finite, the last below the first, the train state on the TPU.
  serve  repro.launch.serve.main on the same configuration: 16 requests
         through 8 continuous-batching slots.  Every request completes,
         the token counts agree, no slot leaks.
  svm    the paper's sparse SVM path at the kdd set's record shape: one
         LIRS-shuffled batch of 1024 records through read_batch_ragged,
         pack_csr_batch and DCDSolver.margins_csr on the chip, against
         float64 margins on the host.

All data and weights come from ``--seed``.  The last line of standard
output is ``{"ok": true, "device": {...}}``; it is printed only on a TPU
and only when every phase passed.  Anything else exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

KDD_FEATURES = 29_890_095  # the kdd set's feature count
KDD_NNZ = (30, 58)  # nonzeros per record: 8 + 8·44 ≈ 362 B on average
SVM_RECORDS, SVM_BATCH = 4096, 1024
TRAIN_RECORDS, TRAIN_SEQ, TRAIN_EPOCHS = 16, 4096, 2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu():
    """The first device JAX finds, which must be a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"chip_smoke needs a TPU; JAX's first device is {dev.platform!r}"
        )
    return dev


def import_repro():
    """Import the package from this checkout's ``src``, and only there."""
    src = os.path.join(ROOT, "src")
    sys.path.insert(0, src)
    import repro  # a namespace package: check every directory it spans

    where = [os.path.abspath(p) for p in repro.__path__]
    check(
        where == [os.path.join(src, "repro")],
        f"repro imported from {where}, not from {src}",
    )


def phase_train(seed: int, dev) -> None:
    from repro.launch.train import main as train_main

    record_bytes = 4 * (TRAIN_SEQ + 1)
    cache_mb = TRAIN_RECORDS * record_bytes / 4 / 2**20
    steps = TRAIN_RECORDS * TRAIN_EPOCHS
    s = train_main([
        "--arch", "granite-3-8b", "--layers", "1",
        "--seq-len", str(TRAIN_SEQ), "--batch", "1",
        "--num-records", str(TRAIN_RECORDS),
        "--epochs", str(TRAIN_EPOCHS), "--steps", str(steps),
        "--shuffler", "lirs", "--eviction-policy", "belady",
        "--cache-mb", repr(cache_mb), "--seed", str(seed),
        # the launcher's 1e-3 suits its CPU widths; at d_model 4096 it
        # diverges within two epochs, so use AdamW's own default
        "--lr", "3e-4",
    ])
    losses = s["losses"]
    print(f"[train] steps {s['steps']}, loss first {losses[0]!r} "
          f"last {losses[-1]!r}")
    print(f"[train] compile+first step {s['first_step_s']!r} s, median "
          f"step after the first {s['median_step_s']!r} s")
    print(f"[train] peak_bytes_in_use "
          f"{dev.memory_stats()['peak_bytes_in_use']}")
    print(f"[train] cache {json.dumps(s['cache'])}")
    check(s["steps"] == steps and len(losses) == steps,
          f"train ran {s['steps']} of {steps} steps")
    check(all(map(math.isfinite, losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], "loss did not fall")
    check(s["state_platforms"] == ["tpu"],
          f"train state on {s['state_platforms']}")
    cache = s["cache"]
    check(cache["demand_hits"] + cache["window_hits"] > 0,
          "the Belady tier served no hits")
    check(cache["rejected_inserts"] == 0 and cache["stray_unpins"] == 0,
          "the DRAM tier rejected inserts or unpinned strays")


def phase_serve(seed: int) -> None:
    from repro.launch.serve import main as serve_main

    requests = 16
    r = serve_main([
        "--arch", "granite-3-8b", "--layers", "1",
        "--max-batch", "8", "--prompt-capacity", "128", "--gen", "32",
        "--requests", str(requests), "--cache-mb", "0.01",
        "--seed", str(seed),
    ])
    print(f"[serve] {r['requests']} of {requests} requests, "
          f"{r['generated_tokens']} tokens in {r['decode_steps']} decode "
          f"steps, {r['slot_leaks']} slot leaks")
    check(r["requests"] == requests, "not every request completed")
    check(r["generated_tokens"] == r["completion_tokens"],
          "generated_tokens disagrees with the completions")
    check(r["slot_leaks"] == 0, "slots leaked")
    fc = r["feature_cache"]
    check(fc["hits"] + fc["misses"] > 0, "no feature reads were served")


def phase_svm(seed: int) -> None:
    import numpy as np

    from repro.core.location import LocationGenerator
    from repro.core.shuffler import LIRSShuffler
    from repro.data.synthetic import make_classification_dataset
    from repro.storage.record_store import RecordStore
    from repro.svm.dcd import DCDSolver
    from repro.svm.sparse import pack_csr_batch, pad_csr

    with tempfile.TemporaryDirectory(prefix="chip_smoke_svm_") as d:
        meta = make_classification_dataset(
            f"{d}/kdd.rrec", SVM_RECORDS, KDD_FEATURES, sparse=True,
            nnz_range=KDD_NNZ, seed=seed,
        )
        store = RecordStore(meta.path)
        try:
            LocationGenerator().generate(store)  # the records' offsets
            idx = next(LIRSShuffler(SVM_RECORDS, SVM_BATCH, seed=seed)
                       .epoch_batches(0))
            csr = pack_csr_batch(
                store.read_batch_ragged(idx, workers=4), KDD_FEATURES
            )
            # the per-record reader parsed record by record is the oracle
            # for the ragged read and the vectorized packing
            want_csr = pack_csr_batch(store.read_batch(idx), KDD_FEATURES)
        finally:
            store.close()
    for got, want in zip(csr, want_csr):
        check(np.array_equal(got, want), "ragged batch differs per record")

    solver = DCDSolver(KDD_FEATURES, SVM_RECORDS)
    solver.w = np.random.default_rng(seed).normal(size=KDD_FEATURES)
    t0 = time.perf_counter()
    margins = solver.margins_csr(csr)
    seconds = time.perf_counter() - t0

    # float64 reference over the float32 weights the chip sees
    idx2d, val2d = pad_csr(csr)
    w32 = solver.w.astype(np.float32).astype(np.float64)
    terms = val2d.astype(np.float64) * w32[idx2d]
    want = terms.sum(-1)
    # Each margin is K float32 products summed in float32, in any order;
    # the standard bound on that error is gamma_(K+1) · Σ|terms| with
    # gamma_n = n·u / (1 − n·u), u = 2^-24 (K = padded row width ≤ 64)
    n = idx2d.shape[1] + 1
    tol = n * 2.0**-24 / (1 - n * 2.0**-24) * np.abs(terms).sum(-1)
    err = np.abs(margins.astype(np.float64) - want)
    print(f"[svm] {len(csr)} records, {csr.nnz} nonzeros, K {idx2d.shape[1]}"
          f", D {KDD_FEATURES}, mean record {meta.avg_record_bytes!r} B; "
          f"margins in {seconds!r} s (compile included)")
    print(f"[svm] max |margin error| {float(err.max())!r}, max "
          f"error/tolerance {float((err / tol).max())!r}")
    check(margins.shape == (SVM_BATCH,), f"margins shape {margins.shape}")
    check(bool(np.isfinite(margins).all()), "non-finite margins")
    check(bool((err <= tol).all()), "margins outside the float32 bound")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu()
    import_repro()
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "writes": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["writes"] += 1

    jax.monitoring.register_event_listener(count)
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    for name, phase in (
        ("train", lambda: phase_train(args.seed, dev)),
        ("serve", lambda: phase_serve(args.seed)),
        ("svm", lambda: phase_svm(args.seed)),
    ):
        t0 = time.perf_counter()
        phase()
        gc.collect()  # release the phase's device buffers before the next
        print(f"[{name}] passed in {time.perf_counter() - t0!r} s")

    print(f"[compile cache] {cache_dir}: {cache_events['hits']} hits, "
          f"{cache_events['writes']} entries written")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
