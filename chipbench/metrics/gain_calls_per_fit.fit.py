"""LMBR gain evaluations (``Placement.stats["gain_calls"]``) per fit,
averaged over the fits of the window."""


def read(run):
    plans = run.window.get("plans")
    if not plans:
        return None
    return sum(p[3]["gain_calls"] for p in plans) / len(plans)
