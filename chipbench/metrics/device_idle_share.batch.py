"""device_idle_share.batch: share of the traced window in which no
operation ran on the device, in percent."""


def read(rec):
    tr = rec.get("trace")
    if rec["mode"] != "batch" or not tr or tr["idle_share"] is None or not tr["n_devices"]:
        return None
    return tr["idle_share"] * 100.0
