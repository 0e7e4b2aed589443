"""Device layer: the share of the traced window in which no operation ran
on the device, averaged over the cell's chips (the contract's
1 - busy_s / window_s, from the same numbers)."""


def read(view):
    if not view.devices or view.window_ns <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)
