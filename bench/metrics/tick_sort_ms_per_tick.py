"""Device time per source tick in the updaters' sort phase (scope
``apply.sort``: the sort by key and timestamp, run boundaries, ``lift``,
delta masking, and the generic path's segmented combine).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "apply.sort")
