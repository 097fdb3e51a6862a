"""Device time of the decode program per decode step, in the trace.  The
engine's prefill, slot-write and decode programs are all jitted lambdas
and share a name; the decode program is the one of them that runs once
per decode step, the most often."""
from benchmarks.chip.trace_reduce import split_by_calls


def read(w):
    found = split_by_calls(w.trace, "jit__lambda", w.counts.get("traced_decodes"))
    if found is None:
        return None
    (calls, seconds), _ = found
    return 1e3 * seconds / calls
