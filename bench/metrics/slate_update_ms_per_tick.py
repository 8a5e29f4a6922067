"""Device time of the fused slate-update kernel (both updaters) per
source tick, mean over the cell's chips (ms)."""

KERNEL = r"^slate_update"


def read(run):
    t = run.op_s(KERNEL)
    if t <= 0 or run.traced_ticks <= 0:
        return None
    return 1e3 * t / run.traced_ticks
