"""The grouped expert matmuls' share of the chip's roofline: their
operations and bytes a step, forward and backward, over the held slots
(benchmarks/chip/moe_flops.py), at the least time the chip could take
over their traced device time a step."""
from benchmarks.chip.flops import roofline_seconds


def read(w):
    m = (w.trace or {}).get("moe")
    if not m or not m["steps"] or not m["gmm_s"]:
        return None
    least = roofline_seconds(m["gmm_flops"], m["gmm_bytes"], m["peaks"])
    return 100.0 * least / (m["gmm_s"] / m["steps"])
