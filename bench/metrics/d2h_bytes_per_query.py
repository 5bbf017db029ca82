"""Bytes the device backend read back from the device in the window (its
``d2h_bytes`` counter), per query completed in it."""


def read(rec):
    moved = rec["backend"].get("d2h_bytes")
    if moved is None or not rec["completed"]:
        return None
    return moved / rec["completed"]
