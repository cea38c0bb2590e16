"""Device bytes of the index state (the sum of its leaves' nbytes) per
page indexed: what a deployment pays in HBM for each page."""


def read(run):
    return run.index_bytes / run.pages
