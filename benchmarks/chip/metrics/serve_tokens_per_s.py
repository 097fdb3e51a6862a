"""Tokens on the host by the window's close, over the window."""
from benchmarks.chip.stats import rate


def read(w):
    return rate(w.counts["tokens"], w.counts["window_s"])
