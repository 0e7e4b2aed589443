"""Local kernels layer: the least time a sort of n 4-byte keys can take on
the chips, one read and one write of the keys at the HBM peak, over the
device's busy time per sort on the busiest chip.  It counts the work of the
problem, not of the sorting network, so it reads the same whatever sorts."""
from bench.trace import length

KEY_BYTES = 4


def read(view):
    dev = view.busiest()
    if dev is None or not view.calls:
        return None
    busy_per_sort = length(view.in_calls(view.busy[dev])) * 1e-9 \
        / len(view.calls)
    if busy_per_sort <= 0:
        return None
    least = 2 * KEY_BYTES * view.n / view.chips / view.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy_per_sort
