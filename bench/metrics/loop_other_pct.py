"""Share of the window's wall time outside negotiation passes and
reconciles: the event engine, backends, autoscalers and the service's
event thread (`WallClockDriver`)."""
from bench.metrics._window import union_s


def read(win):
    inside = union_s(win.probe.passes + win.probe.reconciles)
    return 100.0 * (1.0 - inside / win.window_s)
