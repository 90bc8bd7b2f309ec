"""Mean `apply` phase of the window's device-path passes, from the
program's cycle profiler (host clock)."""
from bench.metrics._window import mean_phase_ms


def read(win):
    return mean_phase_ms(win, "apply_s")
