"""Compilations inside the window: fresh padding buckets the matchmaker
reports (``repro_matchmaker_jit_compiles_total``) plus XLA compile
events from JAX's monitoring.  It should read 0."""


def read(win):
    fresh = (win.counters_after["jit_compiles"]
             - win.counters_before["jit_compiles"])
    return float(fresh + win.compiles)
