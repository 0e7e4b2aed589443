"""Collective phases layer: the share of the busiest chip's collective time
in which no other operation runs on that chip."""
from bench.trace import length, subtract


def read(view):
    dev = view.busiest()
    if dev is None or not view.calls:
        return None
    coll = view.in_calls(view.collectives(dev))
    total = length(coll)
    if total <= 0:
        return None
    return 100.0 * length(subtract(coll, view.others(dev))) / total
