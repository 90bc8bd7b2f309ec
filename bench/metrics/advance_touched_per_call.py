"""Workers the event engine visited per advance of its continuous state
(program counters ``repro_advance_workers_touched_total`` over
``repro_advance_calls_total``): the difference of ``advance_touched``
over that of ``advance_calls`` between the window's first and last
cycle records that carry them, beside the ``engine_s`` snapshots the
span readers use.  A program without the counters reports nothing."""
from bench.metrics._spans import engine_delta


def read(win):
    recs = [c for c in win.cycles if "advance_calls" in c]
    if len(recs) < 2 or engine_delta(win) is None:
        return None
    first, last = recs[0], recs[-1]
    calls = last["advance_calls"] - first["advance_calls"]
    if calls <= 0:
        return None
    return (last["advance_touched"] - first["advance_touched"]) / calls
