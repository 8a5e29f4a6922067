"""Device time per source tick in the tick's telemetry outside its kernels
(scope ``tick.telemetry``: the count-min sketch's hashing and the
latency histograms' bucketing and masks; the ``countmin_update`` and
``histogram_update`` kernels are left out).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "tick.telemetry")
