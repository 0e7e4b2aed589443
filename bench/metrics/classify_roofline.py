"""Local kernels layer: the partition kernel's share of its roofline.

The least time of the classify: at each RAMS level (one at p <= 16), one
read of the n / chips 8-byte keys and one write of a 4-byte bucket id for
each, at the HBM peak of ``bench/peaks.json``.  It is divided by the device
time per ``psort`` call of the ops whose label starts with the kernel's
name (its ``pallas_call``'s ``name=``), on the chip where they take the
longest.  The bytes are the work of the problem, so the share reads the
same whatever classifies.  None where no such op is in the trace."""
from bench.trace import length, union

KERNEL = "partition_planes"
KEY_BYTES = 8
BUCKET_BYTES = 4
LEVELS = 1


def read(view):
    if not view.devices or not view.calls:
        return None
    per_chip = [length(view.in_calls(union(
        [(s, e) for name, s, e in view.trace.devices[d]
         if name.startswith(KERNEL)]))) for d in view.devices]
    kernel_s = max(per_chip) * 1e-9 / len(view.calls)
    if kernel_s <= 0:
        return None
    least = LEVELS * (KEY_BYTES + BUCKET_BYTES) * view.n / view.chips \
        / view.peaks["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
