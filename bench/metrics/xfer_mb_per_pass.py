"""Mean megabytes (1e6 bytes) copied between host and device per
device-path pass, both directions: the nbytes of the padded arrays the
pass's matchmaker calls sent and fetched (program counter)."""
from bench.metrics._spans import mean_device


def read(win):
    mb = mean_device(win, lambda c: c["h2d_bytes"] + c["d2h_bytes"])
    return None if mb is None else mb / 1e6
