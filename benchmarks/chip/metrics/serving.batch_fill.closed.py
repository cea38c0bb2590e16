"""Real rows over padded rows of the batches the server ran in the
window, in % (server counters `stats()["rungs"]`)."""


def read(run):
    rungs = run.server_stats.get("rungs", {})
    padded = sum(v["batches"] * b for b, v in rungs.items())
    real = sum(v["occupancy"] * v["batches"] * b for b, v in rungs.items())
    return 100.0 * real / padded if padded else None
