"""Queries answered per second of the window (closed loop): each
request counts by the share of its time from send to answer that lay in
the window, so one answered in the window counts 1 and a batch still
running at the window's close counts for the part of it that ran there.
Failed requests count nothing."""


def read(run):
    done = 0.0
    for r in run.records:
        if "result" not in r or r["done"] is None:
            continue
        span = r["done"] - r["sent"]
        inside = min(r["done"], run.t1) - max(r["sent"], run.t0)
        done += 1.0 if span <= 0 else max(0.0, inside) / span
    return done / run.seconds
