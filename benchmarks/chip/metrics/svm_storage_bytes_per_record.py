"""Bytes read from the record file per record delivered
(``IOStats.bytes_read``, an exact count)."""


def read(w):
    c = w.counts
    return c["storage_bytes"] / c["records"] if c["records"] else None
