"""Seconds from process start to the window's start: data, session and warm-up."""


def read(rec):
    return rec["setup_s"]
