"""95th percentile of the wall time of every negotiation pass begun in
the window (host clock around `Collector.run_cycle` / `flush_staged`)."""
from bench.metrics._window import p95_ms


def read(win):
    return p95_ms(win.probe.passes)
