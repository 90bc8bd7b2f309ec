"""Mean device round trip per device-path pass (``repro.device.roundtrip``
summed over the pass's matchmaker calls: host-to-device copies,
dispatch, device time and the copy of the answer back), host clock."""
from bench.metrics._spans import mean_device


def read(win):
    ms = mean_device(win, lambda c: c["roundtrip_s"])
    return None if ms is None else ms * 1e3
