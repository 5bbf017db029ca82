"""Device program shapes first compiled or launched inside the window: the
window's rise in the backend's ``program_keys`` (the shapes compiled when
the session opened, and any launched outside them). 0 when every launch
drew its shape from the session's ladder."""


def read(rec):
    return rec["backend"].get("program_keys")
