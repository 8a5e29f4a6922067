"""Device idle time per source tick while the drive loop waits for a
chunk's throttle trace (span ``chunk_sync``).
Mean over chips (ms).  The four ``idle_*_ms_per_tick`` metrics partition
the idle time ``idle_share.flood`` reads."""
from bench import scopes


def read(run):
    return scopes.idle_ms(run, "sync")
