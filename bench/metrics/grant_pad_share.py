"""Share of the grant slots the window's chain stage launches carried that
were padding: the backend's ``grant_padded_rows`` over ``grant_rows``
(each launch with grants carries a fixed block of them)."""


def read(rec):
    carried = rec["backend"].get("grant_rows")
    if not carried:
        return None
    return 100.0 * rec["backend"].get("grant_padded_rows", 0) / carried
