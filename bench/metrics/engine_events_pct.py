"""Share of the driver's running wall spent in event handlers' own code
(``repro.event`` self time, less the passes, reconciles and advances
nested inside): backend ticks, job intake, metrics, stragglers."""
from bench.metrics._spans import engine_pct


def read(win):
    return engine_pct(win, lambda part: part.startswith("event:"))
