"""The whole train step's share of the chip's bf16 peak: the step's
operations per token (benchmarks/chip/flops.py) times the window's
tokens per second."""


def read(w):
    c = w.counts
    return 100.0 * c["flops_per_token"] * c["tokens"] / c["window_s"] / \
        c["peak_flops"]
