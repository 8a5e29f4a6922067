"""Device time per source tick in the tick's queue phase (scope
``tick.queues``: both deliveries with enqueue and overflow policy, and
each operator's dequeue).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "tick.queues")
