"""Device time per source tick in device operations under none of the
tick's scopes and no Pallas kernel: copies XLA inserts, the chunk loop's
own glue, source staging and the host's eager dispatches.
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "unscoped")
