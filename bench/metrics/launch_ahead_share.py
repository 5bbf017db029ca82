"""Share of the window's probe and chain launches that the host issued
while an earlier pipeline of the same unit still held an uncollected
launch: the engine's ``ahead_launches`` counter over the backend's
``device_launches``. A program without the counter reads nothing."""


def read(rec):
    launches = rec["backend"].get("device_launches")
    ahead = rec["counters"].get("ahead_launches")
    if not launches or ahead is None:
        return None
    return 100.0 * ahead / launches
