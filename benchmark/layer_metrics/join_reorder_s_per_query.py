"""join_reorder_s_per_query: host seconds a query inside the planner's join
ordering (program_counter: what the histogram `planner.reorder_joins_s`
gained in the window)."""


def read(spec, window):
    after = window["after"]["histograms"].get(spec["histogram"])
    before = window["before"]["histograms"].get(
        spec["histogram"], {"n": 0, "total": 0.0})
    if not after or after["n"] <= before["n"] or not window["completed"]:
        return None
    return (after["total"] - before["total"]) / window["completed"]
