"""Seconds per fit in the span engine's wide buckets: the ``cover.bucket``
spans of two or more 64-item words (``args.words``), in LMBR and in the
plan's spans, over the fits completed in the window.  Nothing to read
where no such span was recorded."""


def read(run):
    spans = [e for e in run.spans if e.get("ph") == "X"
             and e["name"] == "cover.bucket"
             and e.get("args", {}).get("words", 0) >= 2]
    if not spans or not run.window.get("fits"):
        return None
    return sum(e["dur"] for e in spans) * 1e-6 / run.window["fits"]
