"""Share of the window's probe calls that the device programs served, from
the backend's ``kernel_probes`` and ``fallback_probes`` counters."""


def read(rec):
    served = rec["backend"].get("kernel_probes", 0)
    total = served + rec["backend"].get("fallback_probes", 0)
    return 100.0 * served / total if total else None
