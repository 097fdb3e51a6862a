"""Time the train loop blocked on the input pipeline per step
(``PipelineStats.t_wait``)."""


def read(w):
    c = w.counts
    return 1e3 * c["input_wait_s"] / c["steps"] if c["steps"] else None
