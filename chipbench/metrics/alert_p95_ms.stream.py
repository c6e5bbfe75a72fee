"""alert_p95_ms.stream: 95th percentile, over every event due in the window, of
the time from its due time to the return of the tick that delivered its
alerts.  An event never delivered ranks above all others."""
from chipbench.stats import percentile


def read(rec):
    if rec["mode"] != "stream":
        return None
    p = percentile(rec["latencies_s"], 95, n_failed=rec["failed"])
    return None if p is None else p * 1e3
