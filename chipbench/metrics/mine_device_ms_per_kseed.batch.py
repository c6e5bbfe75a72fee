"""mine_device_ms_per_kseed.batch: device time of every program the
window ran (the trace's XLA Modules), per 1,000 seeds mined."""


def read(rec):
    tr = rec.get("trace")
    if rec["mode"] != "batch" or not tr or not tr["program_s"] or not rec["seeds"]:
        return None
    return sum(tr["program_s"].values()) * 1e3 / rec["seeds"] * 1e3
