"""Time the scoring loop blocked on the input pipeline per batch
(``PipelineStats.t_wait``)."""


def read(w):
    c = w.counts
    return 1e3 * c["input_wait_s"] / c["batches"] if c["batches"] else None
