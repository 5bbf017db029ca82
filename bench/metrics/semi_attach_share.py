"""Share of the window's semi-join boundaries that attached to a semi state
another query built: the engine's ``semi_attaches`` over ``semi_attaches``
plus ``semi_builds``."""


def read(rec):
    attaches = rec["counters"].get("semi_attaches")
    builds = rec["counters"].get("semi_builds")
    if attaches is None or builds is None or not attaches + builds:
        return None
    return 100.0 * attaches / (attaches + builds)
