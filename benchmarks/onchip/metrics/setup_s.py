"""Seconds from process start to the window: data, build, save, open, warm-up."""


def read(rec):
    return rec["setup_s"]
