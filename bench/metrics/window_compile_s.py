"""Seconds that the window's compilations took: what the window lost to
compiling or to reading the persistent cache."""


def read(rec):
    return sum(e["seconds"] for e in rec["window_compiles"])
