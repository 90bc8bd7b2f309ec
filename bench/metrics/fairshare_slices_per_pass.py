"""Mean slices per fair-share pass in the window (the cycle records'
``slices``: one quantum-capped water-fill for one (schedd, user) entry
each; ``repro_fairshare_slices_total``).  Nothing to read from a program
whose records carry no slice count."""
import numpy as np


def read(win):
    vals = [c["slices"] for c in win.cycles
            if c["kind"] == "fairshare" and "slices" in c]
    return float(np.mean(vals)) if vals else None
