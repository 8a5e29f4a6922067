"""Device idle time per source tick while the host is in none of the drive
loop's chunk spans: boundary work (telemetry observe, state republish),
``Engine.run``'s entry and exit, and the caller's time between
``App.run`` calls.
Mean over chips (ms).  The four ``idle_*_ms_per_tick`` metrics partition
the idle time ``idle_share.flood`` reads."""
from bench import scopes


def read(run):
    return scopes.idle_ms(run, "outside")
