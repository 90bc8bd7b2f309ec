"""Share of the window's negotiation passes that took the legacy host
walk (``repro_cycles_total{kind="legacy"}`` over all kinds)."""


def read(win):
    a, b = win.counters_before["cycles"], win.counters_after["cycles"]
    delta = {k: v - a.get(k, 0.0) for k, v in b.items()}
    total = sum(delta.values())
    if total <= 0:
        return None
    return 100.0 * delta.get("legacy", 0.0) / total
