"""Share of the driver's running wall that no program span covers: the
driver loop's own bookkeeping between spans."""
from bench.metrics._spans import engine_pct


def read(win):
    covered = engine_pct(win, lambda part: True)
    return None if covered is None else 100.0 - covered
