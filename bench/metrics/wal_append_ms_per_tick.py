"""Time per source tick in write-ahead log appends (span ``wal_append``
on the log's writer thread: one record per tick), clipped to the traced
span (ms)."""
from bench import durable


def read(run):
    return durable.span_ms(run, "wal_append")
