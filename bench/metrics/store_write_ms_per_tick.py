"""Time per source tick in the slate store's writes of flushed rows
(span ``store_write`` on the flusher thread: one compressed columnar
block per updater), clipped to the traced span (ms)."""
from bench import durable


def read(run):
    return durable.span_ms(run, "store_write")
