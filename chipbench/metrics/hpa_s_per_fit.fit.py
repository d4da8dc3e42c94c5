"""Seconds per fit in HPA, the LMBR start: the outermost ``fit.hpa``
spans of the program's tracer over the window, over the fits completed
in it.  ``span_s_per_fit`` is shared with the other span readers."""


def span_s_per_fit(run, name):
    """Seconds of the outermost spans named ``name`` in ``run.spans``
    (those with no enclosing span of that name, by ``args.parent``) per
    fit of the window; ``None`` where no such span was recorded."""
    xs = [e for e in run.spans if e.get("ph") == "X"]
    by_id = {e["args"]["id"]: e for e in xs if "id" in e.get("args", {})}

    def nested(e):
        p = by_id.get(e.get("args", {}).get("parent"))
        while p is not None:
            if p["name"] == name:
                return True
            p = by_id.get(p["args"].get("parent"))
        return False

    spans = [e for e in xs if e["name"] == name and not nested(e)]
    if not spans or not run.window.get("fits"):
        return None
    return sum(e["dur"] for e in spans) * 1e-6 / run.window["fits"]


def read(run):
    return span_s_per_fit(run, "fit.hpa")
