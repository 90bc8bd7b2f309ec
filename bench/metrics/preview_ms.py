"""Mean wall time per reconcile spent in the collector's preview
(``repro_reconcile_preview_seconds``), over the window's reconciles."""


def read(win):
    a, b = win.counters_before, win.counters_after
    n = b["preview_count"] - a["preview_count"]
    if n <= 0:
        return None
    return 1e3 * (b["preview_sum"] - a["preview_sum"]) / n
