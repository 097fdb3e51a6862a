"""Records whose margins are back on the host in the window, over the
window."""
from benchmarks.chip.stats import rate


def read(w):
    return rate(w.counts["records"], w.counts["window_s"])
