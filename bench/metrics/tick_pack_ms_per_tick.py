"""Device time per source tick in the packed path's table work around the
``slate_update`` kernel (scope ``apply.pack``: fresh-slot zeroing, the
``[B, D]`` and ``[C, D]`` pack, the unpack).
Mean over chips (ms).  The eight ``tick_*_ms_per_tick`` metrics read by
``bench/scopes.py`` partition ``tick_other_ms_per_tick``."""
from bench import scopes


def read(run):
    return scopes.device_ms(run, "apply.pack")
