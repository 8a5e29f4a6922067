"""Device time per source tick in the updaters' writes (scope
``apply.write``: the ts/dirty scatter, the generic and sequential paths'
slate read/merge/write, TTL sweeps).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "apply.write")
