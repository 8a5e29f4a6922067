"""Device time of the telemetry kernels (count-min sketch and latency
histogram, both updaters) per source tick, mean over chips (ms)."""

KERNELS = r"^(countmin_update|histogram_update)"


def read(run):
    t = run.op_s(KERNELS)
    if t <= 0 or run.traced_ticks <= 0:
        return None
    return 1e3 * t / run.traced_ticks
