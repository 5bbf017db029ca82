"""Queries completed inside the window, over the window's seconds."""


def read(rec):
    return rec["completed"] / rec["seconds"]
