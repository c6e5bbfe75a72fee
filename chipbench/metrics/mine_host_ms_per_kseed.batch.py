"""mine_host_ms_per_kseed.batch: host time inside the window's
MiningSession.mine calls not blocked on the device (the program's phase
counters ``mine_ns`` less ``wait_ns``, summed in MiningResult.stats),
per 1,000 seeds; nothing to read where the program keeps no such
counters."""


def read(rec):
    if rec["mode"] != "batch" or not rec["seeds"]:
        return None
    stats = rec["stats"]
    if "mine_ns" not in stats or "wait_ns" not in stats:
        return None
    return (stats["mine_ns"] - stats["wait_ns"]) / 1e6 / rec["seeds"] * 1e3
