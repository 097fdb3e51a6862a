"""The whole scoring step's share of the chip's bf16 peak: ``csr_dot``'s
operations per batch times the window's batches per second."""
from benchmarks.chip.flops import csr_dot_flops


def read(w):
    c = w.counts
    return 100.0 * csr_dot_flops(c["batch"], c["k"]) * c["batches"] / \
        c["window_s"] / c["peaks"]["bf16_flops_per_s"]
