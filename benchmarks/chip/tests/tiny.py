"""A tiny copy of the benchmark for the tests: the program's small
granite variant, a few hundred sparse records, and short windows.  Each
cell keeps its driver, its checks and its metric readers; only the sizes
shrink."""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

GRANITE = {
    "name": "granite-tiny", "arch": "granite-3-8b", "smoke": True,
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "num_hidden_layers": 2, "hidden_act": "silu", "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
    "compute_dtype": "bfloat16", "param_dtype": "float32",
}
SVM = {
    "name": "svm-tiny", "num_features": 100_000, "num_records": 1024,
    "nnz_min": 20, "nnz_max": 39, "margin_dtype": "float32",
}


def _traffic(name: str, **changes):
    with open(HERE / "traffic" / f"{name}.json") as f:
        t = json.load(f)
    t.update(changes)
    return t


TRAFFIC = {
    # the smoke widths round differently from the published ones: their
    # limits sit between the CPU readings of sound runs (loss, gradient
    # and change gaps up to 7.5e-5, 2.2e-3, 1.6e-3 over four seeds) and
    # of the float8 control (at least 1.2e-2 on the gradient)
    "train-tiny": _traffic("train-4k-lirs", records=64, seq_len=32, batch=2,
                           io_workers=2, limits={
                               "loss_gap": 8e-4, "grad_norm_gap": 5e-3,
                               "change_norm_gap": 5e-3}),
    "score-tiny": _traffic("score-lirs-tier25", batch=256, io_workers=2),
    "serve-tiny": _traffic(
        "serve-chat-0.8knee", rate=8.0, slots=4,
        prompt={"median": 8, "sigma": 1.0, "min": 4, "max": 32},
        output={"median": 6, "sigma": 0.8, "min": 2, "max": 16},
        # the tied head at d_model 64 gives logits of a twentieth of the
        # published widths' scale: sound runs read up to 2.2e-3 over five
        # seeds on the CPU, the float8 control 2.5e-2 and more
        check_requests=4, check_tokens=8,
        limits={"served_logit_gap": 8e-3}),
}


def make(tmp: Path):
    """Write the tiny benchmark under ``tmp``; returns the parsed
    ``BENCHMARK.json`` and the directory its files are found in."""
    with open(HERE.parents[1] / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench_dir = tmp / "bench"
    (bench_dir / "traffic").mkdir(parents=True)
    (bench_dir / "configs").mkdir()
    os.symlink(HERE / "metrics", bench_dir / "metrics")
    for name, t in TRAFFIC.items():
        with open(bench_dir / "traffic" / f"{name}.json", "w") as f:
            json.dump(t, f)
    configs = []
    for c in (GRANITE, SVM):
        path = bench_dir / "configs" / f"{c['name']}.json"
        with open(path, "w") as f:
            json.dump(c, f)
        configs.append({"name": c["name"], "file": str(path)})
    cells = {w["name"]: w for w in bench["workloads"]}
    bench["configs"] = configs
    bench["workloads"] = [
        dict(cells["granite-3-8b.train-4k-lirs"], config="granite-tiny",
             traffic="train-tiny"),
        dict(cells["svm-kdd2010.score-lirs-tier25"], config="svm-tiny",
             traffic="score-tiny"),
        dict(cells["granite-3-8b.serve-chat-0.8knee"], config="granite-tiny",
             traffic="serve-tiny"),
    ]
    return bench, bench_dir


def args(workload: str, seed: int = 1234567890123, seconds: float = 2.0,
         trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
