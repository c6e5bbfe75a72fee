"""alert_p50_ms: median, over every event due in the window, of the time
from its due time to the return of the tick that delivered its alerts
and witnesses: how stale an alert is when it reaches the queue.  An
event never delivered ranks above all others."""
from chipbench.stats import percentile


def read(rec):
    if rec["mode"] != "stream":
        return None
    p = percentile(rec["latencies_s"], 50, n_failed=rec["failed"])
    return None if p is None else p * 1e3
