"""Host wrapper layer: per ``psort`` call, the time in the program's
``psort.pull`` span (the copy of the padded per-PE outputs to the host),
summed inside the harness's span around the call; the mean over the traced
calls, in ms.  None where the program writes no such span."""
from bench.trace import length, union

SPAN = "psort.pull"


def read(view):
    spans = [(s, e) for name, s, e in view.trace.host if name == SPAN]
    if not view.devices or not view.calls or not spans:
        return None
    return length(view.in_calls(union(spans))) * 1e-6 / len(view.calls)
