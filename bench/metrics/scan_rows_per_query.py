"""Rows the engine's shared scans read in the window (its ``scan_rows``
counter), per query completed in the window. Sharing lowers it."""


def read(rec):
    if not rec["completed"]:
        return None
    return rec["counters"]["scan_rows"] / rec["completed"]
