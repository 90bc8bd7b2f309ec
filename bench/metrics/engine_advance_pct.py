"""Share of the driver's running wall spent integrating continuous state
(job completions, worker clocks) before events: the ``repro.advance``
span's self time."""
from bench.metrics._spans import engine_pct


def read(win):
    return engine_pct(win, lambda part: part == "advance")
