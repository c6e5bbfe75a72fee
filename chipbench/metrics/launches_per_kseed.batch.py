"""launches_per_kseed.batch: kernel launches (MiningResult.stats
kernel_calls) of the window's mines per 1,000 seeds."""


def read(rec):
    if rec["mode"] != "batch" or not rec["seeds"]:
        return None
    return rec["stats"]["kernel_calls"] / rec["seeds"] * 1e3
