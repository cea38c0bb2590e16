"""Seconds from process start to the start of the measured window:
build, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
