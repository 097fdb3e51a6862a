"""The device's idle share of the traced span: one less the union of
its operations' intervals over the span."""
from benchmarks.chip.trace_reduce import idle_share


def read(w):
    return idle_share(w.trace)
