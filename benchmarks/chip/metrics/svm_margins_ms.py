"""Host-clock time of ``DCDSolver.margins_csr`` per batch; the call ends
in a copy to the host, so it is synced."""


def read(w):
    c = w.counts
    return 1e3 * c["margins_s"] / c["batches"] if c["batches"] else None
