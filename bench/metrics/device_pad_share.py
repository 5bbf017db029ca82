"""Share of the rows the window's probe and chain launches ran that were
padding: every launch pads its rows to a power of two, from the backend's
``device_rows`` and ``device_padded_rows`` counters."""


def read(rec):
    padded = rec["backend"].get("device_padded_rows")
    if not padded:
        return None
    return 100.0 * (padded - rec["backend"]["device_rows"]) / padded
