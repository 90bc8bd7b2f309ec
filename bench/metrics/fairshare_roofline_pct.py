"""Share of the fair-share program's HBM roofline: the bytes any
implementation of the traced window's fair-share passes must move
(`bench.roofline_fairshare`, from the cycle records), over the chip's
HBM bandwidth (`bench/peaks.json`), over the device time outside the
three probed entry points (trace busy time less ``entry_device_s``),
which in a fair-share cell is the fair-share program's.  The traced
passes are the window's records that began within the trace's length
of the first: the service stops while the trace is written, so the
rest begin after that gap.  Nothing to read where no such record or no
such device time is."""
from bench import roofline, roofline_fairshare


def read(win):
    if win.trace is None or not win.cycles:
        return None
    device_s = (win.trace["busy_s"] or 0.0) - sum(
        win.trace["entry_device_s"].values())
    end = win.cycles[0]["w0"] + win.trace["window_s"]
    moved = sum(roofline_fairshare.pass_bytes(c) for c in win.cycles
                if c["kind"] == "fairshare" and "nonzero_takes" in c
                and c["w0"] < end)
    if device_s <= 0 or moved <= 0:
        return None
    bw = roofline.peaks(win.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (moved / bw) / device_s
