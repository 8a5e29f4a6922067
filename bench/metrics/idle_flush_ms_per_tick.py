"""Device idle time per source tick while the drive thread is at a flush
boundary: ``flush_begin`` (drain ticks, the tables' snapshot copies, the
log's fence), ``flush_commit`` (the snapshot's transfer, handing rows to
the flusher, waiting for the store write) or ``wal_fence``.
Mean over chips (ms).  Part of the idle time that
``idle_outside_ms_per_tick`` reads."""
from bench import durable


def read(run):
    return durable.idle_flush_ms(run)
