"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window)."""


def read(win):
    if win.trace is None or win.trace["busy_s"] is None:
        return None
    return 100.0 * (1.0 - win.trace["busy_s"] / win.trace["window_s"])
