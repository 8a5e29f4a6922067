"""Device time per source tick in the tables' probes (scope
``apply.probe``: every round of ``insert_or_find``, its lookups and
claim scatters).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "apply.probe")
