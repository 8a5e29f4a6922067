"""Share of its roofline the fused slate-update kernel reaches (%): the
bytes its algorithm needs for the traced ticks (``bench/work.py``: keys,
slots and deltas in, a read and a write of each touched row) over the
chip's HBM bandwidth, divided by the kernel's traced time.  Its work is
memory-bound: it does no floating-point work worth a FLOP bound."""

KERNEL = r"^slate_update"


def read(run):
    t = run.op_s(KERNEL)
    if t <= 0 or run.slate_update_bytes <= 0:
        return None
    least = run.slate_update_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
