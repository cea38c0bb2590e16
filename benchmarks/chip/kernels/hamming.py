"""The Hamming MaxSim kernel (`kernels/hamming.py`): one scan call over
the pruned b-bit codes (the configuration has no rerank).

Counts as for the ADC kernel: one add and one max for each query token
and code read; the b-bit code payload read once, the query codes, and
the top-k written out; real queries only.
"""
from __future__ import annotations

import math

# the op names of the Pallas call: its jitted wrapper's name
PATTERN = r"hamming_maxsim_pallas"


def search_counts(config: dict, pages: int, real: int):
    """(operations, bytes) of one search of `real` queries."""
    hpc, enc = config["hpc"], config["encoder"]
    mq, md = enc["query_len"], enc["n_patches"]
    kept = max(1, min(md, math.ceil(md * hpc["p"] / 100.0)))
    code_bytes = math.ceil(math.log2(hpc["k"])) / 8
    ops = 2 * real * pages * mq * kept
    nbytes = (pages * kept * code_bytes + real * mq * code_bytes
              + real * config["top_k"] * 8)
    return ops, nbytes
