"""`legacy_pass_pct`, reported in a cell whose window holds too few passes for
an end-to-end tail; it moves `claims_per_s` there."""
from bench.metrics.legacy_pass_pct import read  # noqa: F401
