"""Producer time per batch: read through the DRAM tier, decode and
``pack_csr_batch`` (``PipelineStats.t_load``)."""


def read(w):
    c = w.counts
    return 1e3 * c["load_s"] / c["batches"] if c["batches"] else None
