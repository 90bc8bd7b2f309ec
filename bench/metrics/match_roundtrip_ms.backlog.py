"""`match_roundtrip_ms`, reported in a cell whose window holds too few
passes for an end-to-end tail; it moves `claims_per_s` there."""
from bench.metrics.match_roundtrip_ms import read  # noqa: F401
