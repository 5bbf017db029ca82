"""Device milliseconds of the engine's device programs (those holding the
``graft_chain`` and ``graft_probe`` scopes) in the traced window, per query
completed in it."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not rec["completed"]:
        return None
    return 1000.0 * sum(tr["program_s"].values()) / rec["completed"]
