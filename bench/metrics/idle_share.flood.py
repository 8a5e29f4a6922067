"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips (%)."""


def read(run):
    if not run.ops or run.traced_s <= 0:
        return None
    busy = sum(run.busy_s(d) for d in run.ops) / len(run.ops)
    return 100.0 * (1.0 - busy / run.traced_s)
