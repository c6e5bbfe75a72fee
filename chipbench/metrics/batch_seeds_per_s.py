"""batch_seeds_per_s: every seed of the mines the window ran, over the
window's time (it closes when the last mine returns)."""
from chipbench.stats import rate


def read(rec):
    if rec["mode"] != "batch":
        return None
    return rate(rec["seeds"], rec["window_s"])
