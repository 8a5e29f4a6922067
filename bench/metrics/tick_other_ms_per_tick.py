"""Device busy time per source tick outside the tick's Pallas kernels:
the queues, the mapper, the tables' insert-or-find and sorts, the
[C, D] pack and unpack, source staging (ms)."""

KERNELS = r"^(slate_update|countmin_update|histogram_update)"


def read(run):
    if not run.ops or run.traced_ticks <= 0:
        return None
    busy = sum(run.busy_s(d) for d in run.ops) / len(run.ops)
    return 1e3 * (busy - run.op_s(KERNELS)) / run.traced_ticks
