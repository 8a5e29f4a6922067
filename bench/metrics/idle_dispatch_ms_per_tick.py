"""Device idle time per source tick while the drive loop stacks and
dispatches a chunk (spans ``stack_sources`` and ``chunk_dispatch``:
buffer allocation, argument transfer, the launch).
Mean over chips (ms).  The four ``idle_*_ms_per_tick`` metrics partition
the idle time ``idle_share.flood`` reads."""
from bench import scopes


def read(run):
    return scopes.idle_ms(run, "dispatch")
