"""The ADC MaxSim kernel (`kernels/quantized_maxsim.py`): its scan call
over the pruned codes and its rerank call over the candidates' unpruned
codes.

Counts are of the work, whatever implements it: one add and one max for
each query token and code read, and the stored code payload read once
per call (ceil(log2 K) bits a code) plus the query tables and the top-k
written out. Only real queries count, so padding shows as waste.
"""
from __future__ import annotations

import math

# the op names of the Pallas call: its jitted wrapper's name, which the
# rerank's vmap prefixes (`vmap_jit_quantized_maxsim_pallas__`)
PATTERN = r"quantized_maxsim_pallas"


def search_counts(config: dict, pages: int, real: int):
    """(operations, bytes) of one search of `real` queries."""
    hpc, enc = config["hpc"], config["encoder"]
    mq, md, k = enc["query_len"], enc["n_patches"], hpc["k"]
    kept = max(1, min(md, math.ceil(md * hpc["p"] / 100.0)))
    code_bytes = math.ceil(math.log2(k)) / 8
    table = real * mq * k * 4
    n_cand = config["top_k"] if not hpc["rerank"] else max(
        config["top_k"], hpc["rerank"])
    ops = 2 * real * pages * mq * kept
    nbytes = pages * kept * code_bytes + table + real * n_cand * 8
    if hpc["rerank"]:
        ops += 2 * real * n_cand * mq * md
        nbytes += (real * n_cand * md * code_bytes + table
                   + real * config["top_k"] * 8)
    return ops, nbytes
