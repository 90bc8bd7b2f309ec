"""`xfer_mb_per_pass`, reported in a cell whose window holds too few
passes for an end-to-end tail; it moves `claims_per_s` there."""
from bench.metrics.xfer_mb_per_pass import read  # noqa: F401
