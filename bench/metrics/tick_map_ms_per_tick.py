"""Device time per source tick in the mappers (scope ``tick.map``:
``map_batch`` and the masking of its emissions).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "tick.map")
