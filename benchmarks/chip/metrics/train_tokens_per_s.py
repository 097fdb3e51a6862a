"""Tokens of every step completed in the window, over the window."""
from benchmarks.chip.stats import rate


def read(w):
    return rate(w.counts["tokens"], w.counts["window_s"])
