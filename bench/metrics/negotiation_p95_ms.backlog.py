"""`negotiation_p95_ms`, reported in a cell whose window holds too few passes for
an end-to-end tail; it moves `claims_per_s` there."""
from bench.metrics.negotiation_p95_ms import read  # noqa: F401
