"""Hyperedges the fit ran on (``Placement.stats["edges"]``: the trace's
distinct access sets), averaged over the fits of the window.  Nothing to
read where the program does not report it."""


def read(run):
    plans = run.window.get("plans")
    if not plans or any("edges" not in p[3] for p in plans):
        return None
    return sum(p[3]["edges"] for p in plans) / len(plans)
