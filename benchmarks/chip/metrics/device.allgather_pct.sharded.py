"""Share of the chips' busy time in the window taken by the all-gathers
of the sharded search's merge (`search.shard_merge`), in %: the device
self time of the ops named `all-gather`, or `all-gather-start` and
`all-gather-done` where the compiler splits one into an async pair,
summed over the cell's chips, over their busy time summed likewise.
None on one chip or where no all-gather ran. A v5e 2x2 running this
merge at 4,096 pages named the op `all-gather`; the CPU compiler names
it `all_gather`, and both spellings are taken."""
import re

from benchmarks.chip import readers

ALL_GATHER = re.compile(r"^all[-_]gather([-_]start|[-_]done)?$")


def read(run):
    if len(run.traces) < 2:
        return None
    lo, hi = run.trace.window()
    ns = sum(v for t in run.traces for name, v in t.op_self_ns(lo, hi).items()
             if ALL_GATHER.match(name))
    busy = sum(readers.busy_s_per_chip(run))
    return 100.0 * ns / 1e9 / busy if ns and busy else None
