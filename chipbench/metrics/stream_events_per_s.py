"""stream_events_per_s: events delivered, over the time from the window's
opening to the last delivery."""
from chipbench.stats import rate


def read(rec):
    if rec["mode"] != "stream" or not rec["last_delivery_s"]:
        return None
    return rate(rec["delivered"], rec["last_delivery_s"])
