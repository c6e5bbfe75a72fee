"""tick_p50_ms.stream: median wall time of the window's submit calls."""
import statistics


def read(rec):
    if rec["mode"] != "stream" or not rec["ticks"]:
        return None
    return statistics.median(t["submit_s"] for t in rec["ticks"]) * 1e3
