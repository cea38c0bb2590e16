"""99th percentile of how late the load generator sent a request after
its due time: a starved generator is not read as a fast server."""
from benchmarks.chip import readers


def read(run):
    return readers.percentile(
        [(r["sent"] - r["due"]) * 1e3 for r in run.records if "sent" in r],
        99)
