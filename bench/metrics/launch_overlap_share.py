"""Share of the window's probe and chain launches that the host issued
while another unit's launch was still outstanding: the engine's
``overlapped_launches`` counter over the backend's ``device_launches``."""


def read(rec):
    launches = rec["backend"].get("device_launches")
    if not launches:
        return None
    return 100.0 * rec["counters"].get("overlapped_launches", 0) / launches
