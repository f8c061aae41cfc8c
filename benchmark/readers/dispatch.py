"""Model-plane device dispatches per finished round, from the program's exact
counters (``get_dispatch_counts``), all sites summed; the per-site split is
printed on an earlier line by the driver."""


def read(context):
    shapes = context["shapes"]
    counts, rounds = shapes.get("dispatch_counts"), shapes.get("rounds_done")
    if not counts or not rounds:
        return None
    return sum(counts.values()) / rounds
