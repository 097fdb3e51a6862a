"""Device time of the prefill and slot-write programs per admission, in
the trace: the engine's jitted lambdas other than the decode program
(see serve_decode_ms)."""
from benchmarks.chip.trace_reduce import split_by_calls


def read(w):
    admitted = w.counts.get("traced_prefills")
    found = split_by_calls(w.trace, "jit__lambda", w.counts.get("traced_decodes"))
    if found is None or not admitted:
        return None
    _, rest = found
    return 1e3 * rest / admitted
