"""XLA compilations (persistent-cache reads included) that ended inside the
window. Warm-up should leave none."""


def read(rec):
    return len(rec["window_compiles"])
