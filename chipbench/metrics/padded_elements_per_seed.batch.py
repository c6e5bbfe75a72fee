"""padded_elements_per_seed.batch: padded elements the window's mines
launched (MiningResult.stats padded_elements) per seed."""


def read(rec):
    if rec["mode"] != "batch" or not rec["seeds"]:
        return None
    return rec["stats"]["padded_elements"] / rec["seeds"]
