"""95th percentile, over every request due in the window, of the time
from its due time to its first token on the host; a request that failed
or never got a first token counts as missing any limit."""
from benchmarks.chip.stats import tail


def read(w):
    return 1e3 * tail(w.counts["ttft_s"], 95)
