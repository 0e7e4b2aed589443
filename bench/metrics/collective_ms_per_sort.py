"""Collective phases layer: device time of the collective operations
(all-to-all, all-gather, all-reduce, collective-permute, and each
asynchronous one from its start to its done) on the busiest chip, per
``psort`` call, in ms."""
from bench.trace import length


def read(view):
    dev = view.busiest()
    if dev is None or not view.calls:
        return None
    coll = view.in_calls(view.collectives(dev))
    if not coll:
        return None
    return length(coll) * 1e-6 / len(view.calls)
