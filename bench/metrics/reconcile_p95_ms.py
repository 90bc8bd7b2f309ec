"""95th percentile of the wall time of every provisioner reconcile begun
in the window (host clock around `Provisioner.reconcile`)."""
from bench.metrics._window import p95_ms


def read(win):
    return p95_ms(win.probe.reconciles)
