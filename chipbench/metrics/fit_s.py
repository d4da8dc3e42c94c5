"""Seconds per validated cold plan: the whole window over the whole fits
completed in it."""


def read(run):
    if not run.window.get("fits"):
        return None
    return run.window["elapsed_s"] / run.window["fits"]
