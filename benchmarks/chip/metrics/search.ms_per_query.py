"""Host span around each search call, ending at block_until_ready,
per real query in it. Reads `search.ms_per_query.open` and
`search.ms_per_query.closed`."""
from benchmarks.chip import readers


def read(run):
    return readers.search_ms_per_query(run)
