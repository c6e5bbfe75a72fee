"""full_path_share.stream: share of the window's ticks whose TickReport
path is "full" (the whole live graph re-mined), in percent."""


def read(rec):
    if rec["mode"] != "stream" or not rec["ticks"]:
        return None
    return 100.0 * sum(t["path"] == "full" for t in rec["ticks"]) / len(rec["ticks"])
