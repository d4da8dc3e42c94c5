"""``fit``'s loop (``chipbench/loops/fit.py``) with one more check: every
plan of the window was fit on the whole of its trace, as the plan's own
``stats["queries"]`` counts the queries the fit was given.

For traces whose access sets repeat, either half of a trace holds nearly
every set, so a plan fit on part of a trace reads inside the sound
``excess_span`` band; this check is what catches it.  A program whose plans
do not count their queries cannot be held to it, and the loop refuses such
a program at set-up, before any trace is generated."""

from __future__ import annotations

from ..harness import Check
from . import fit

window = fit.window


def setup(run) -> None:
    from repro.core import PlacementService

    probe = PlacementService("lmbr").fit([[0, 1], [0, 1]], 2, 2, 2.0)
    if "queries" not in (probe.stats or {}):
        raise SystemExit(
            f"{run.cell}: the program's plans do not count the queries they "
            "were fit on (stats['queries']), so this cell cannot check that "
            "each plan was fit on its whole trace")
    fit.setup(run)


def check(run):
    traces, plans = run.state["traces"], run.window["plans"]
    whole = [p for p in plans if p[3]["queries"] == len(traces[p[0]][0])]
    # fit's checks over the plans fit on their whole trace; the others
    # have failed already
    run.window["plans"] = whole
    try:
        checks = fit.check(run)
    finally:
        run.window["plans"] = plans
    partial = len(plans) - len(whole)
    run.window["failed"] += partial
    return checks + [Check("partial_trace", partial, 0)]
