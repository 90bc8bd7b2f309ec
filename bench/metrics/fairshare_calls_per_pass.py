"""Mean matchmaker calls per fair-share pass in the window (the cycle
records' ``device_calls``; ``repro_fairshare_device_calls_total``): the
slice count where each slice is its own device round trip, 1 where the
pass is one device program.  Nothing to read from a program whose
records carry no call count."""
import numpy as np


def read(win):
    vals = [c["device_calls"] for c in win.cycles
            if c["kind"] == "fairshare" and "device_calls" in c]
    return float(np.mean(vals)) if vals else None
