"""Host wrapper layer: per ``psort`` call, the part of the harness's span
around it in which no operation runs on any of the cell's devices; the
mean over the traced calls, in ms."""
from bench.trace import length, subtract


def read(view):
    if not view.calls or not view.devices:
        return None
    busy = view.any_busy()
    idle = [length(subtract([c], busy)) for c in view.calls]
    return sum(idle) / len(idle) * 1e-6
