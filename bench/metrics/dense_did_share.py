"""Share of the window's hash-build rows whose derivation ids the state
resolved by direct address: the engine's ``dense_did_rows`` over
``dense_did_rows`` plus ``hashed_did_rows``. A program without the counters,
or a window with no build rows, reads nothing."""


def read(rec):
    dense = rec["counters"].get("dense_did_rows")
    hashed = rec["counters"].get("hashed_did_rows")
    if dense is None or hashed is None or not dense + hashed:
        return None
    return 100.0 * dense / (dense + hashed)
