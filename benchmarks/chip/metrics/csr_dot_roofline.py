"""``csr_dot``'s share of its roofline: the least time its bytes and
operations need at the chip's peaks (benchmarks/chip/flops.py), over
its device time per call in the trace.  Bound by memory."""
from benchmarks.chip.flops import csr_dot_bytes, csr_dot_flops, roofline_seconds


def read(w):
    p = (w.trace or {}).get("programs", {}).get("jit_csr_dot")
    if not p or not p["calls"]:
        return None
    b, k = w.counts["batch"], w.counts["k"]
    least = roofline_seconds(csr_dot_flops(b, k), csr_dot_bytes(b, k),
                             w.counts["peaks"])
    return 100.0 * least / (p["device_s"] / p["calls"])
