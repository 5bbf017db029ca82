"""Share of the demand of the queries completed in the window that grafted
state represented: the engine's ``graft_represented_rows`` over its
``graft_demand_rows``, each the EXPLAIN GRAFT accounting of a query's
admission summed when the query completes."""


def read(rec):
    demand = rec["counters"].get("graft_demand_rows")
    if not demand:
        return None
    return 100.0 * rec["counters"].get("graft_represented_rows", 0) / demand
