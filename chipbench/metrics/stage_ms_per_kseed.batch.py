"""stage_ms_per_kseed.batch: host time the window's mines spent building
seed buffers and handing them to the device (the program's phase
counter ``stage_ns``, summed in MiningResult.stats), per 1,000 seeds;
nothing to read where the program keeps no such counter."""


def read(rec):
    if rec["mode"] != "batch" or not rec["seeds"] or "stage_ns" not in rec["stats"]:
        return None
    return rec["stats"]["stage_ns"] / 1e6 / rec["seeds"] * 1e3
