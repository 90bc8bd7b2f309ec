"""Share of the water-fill's HBM roofline: the bytes any implementation
of the window's water-fill calls must move (`bench.roofline`), over the
chip's HBM bandwidth (`bench/peaks.json`), over the device time of the
operations run inside those calls (trace).  Nothing to read where the
window ran no water-fill on the device."""
from bench import roofline


def read(win):
    if win.trace is None:
        return None
    device_s = sum(win.trace["entry_device_s"].values())
    moved = sum(win.bytes_moved.values())
    if device_s <= 0 or moved <= 0:
        return None
    bw = roofline.peaks(win.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (moved / bw) / device_s
