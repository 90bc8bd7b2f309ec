"""Swappable matchmaking backends behind one protocol (see base.py).

    from repro.core.matchmaker import make_matchmaker
    mm = make_matchmaker("jax")          # or "numpy", "scan", "pallas"
    plan = mm.match(problem)

Selection flows from `Simulation(matchmaker=...)` / the `[provision]
matchmaker=` INI key through `Collector(matchmaker=...)`; every backend
is claim-for-claim identical on quantity-blind policies (the
differential suite pins it).
"""
from repro.core.matchmaker.base import (
    EXHAUSTIBLE_IDX, FIT_EPS, RESOURCE_KEYS, MatchPlan, MatchProblem,
    Matchmaker, cohort_fits, make_matchmaker, matchmaker_names,
    register_matchmaker,
)
from repro.core.matchmaker.numpy_backend import NumpyMatchmaker
from repro.core.matchmaker.scan_backend import ScanMatchmaker
from repro.core.matchmaker.jax_backend import JaxMatchmaker
from repro.core.matchmaker.pallas_backend import PallasMatchmaker

register_matchmaker("numpy", NumpyMatchmaker)
register_matchmaker("scan", ScanMatchmaker)
register_matchmaker("jax", JaxMatchmaker)
register_matchmaker("pallas", PallasMatchmaker)

__all__ = [
    "EXHAUSTIBLE_IDX", "FIT_EPS", "RESOURCE_KEYS", "JaxMatchmaker",
    "MatchPlan", "MatchProblem", "Matchmaker", "NumpyMatchmaker", "PallasMatchmaker", "ScanMatchmaker",
    "cohort_fits", "make_matchmaker", "matchmaker_names",
    "register_matchmaker",
]
