"""device_idle_share.stream: share of the traced window in which no
operation ran on the device, in percent."""


def read(rec):
    tr = rec.get("trace")
    if rec["mode"] != "stream" or not tr or tr["idle_share"] is None or not tr["n_devices"]:
        return None
    return tr["idle_share"] * 100.0
