"""Bytes the device backend copied from host to device in the window (its
``h2d_bytes`` counter), per query completed in it."""


def read(rec):
    moved = rec["backend"].get("h2d_bytes")
    if moved is None or not rec["completed"]:
        return None
    return moved / rec["completed"]
