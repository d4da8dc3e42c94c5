"""Controls and planted faults: ways to break the timed path underneath a
run, each of which the check has to catch (``correct`` false).

* ``fit_control``: the program's own LMBR with its move budget at 0, so
  the fit returns its balanced HPA start unreplicated (what a change that
  trades moves for fit time would do, taken to the end).
* ``FIT_FAULTS``: a fit whose spans are altered where they are produced;
  a fit that leaves its state unchanged (no LMBR move); a fit over half
  of its trace.  A cell on one chip has no exchange between
  chips to leave out.

``install(name, monkeypatch)`` plants one of them through pytest's
``monkeypatch`` (or any object with its ``setattr``).
"""

from __future__ import annotations

import functools

import numpy as np


def _altered_spans(orig):
    def batched_spans_csr(edge_ptr, edge_nodes, member):
        spans = np.array(orig(edge_ptr, edge_nodes, member))
        if len(spans):
            spans[0] += 1
        return spans

    return batched_spans_csr


def _half_trace(orig):
    def fit(self, queries, *args, **kw):
        return orig(self, queries[: (len(queries) + 1) // 2], *args, **kw)

    return fit


def install(name: str, mp) -> None:
    """Plant control or fault ``name`` with ``mp.setattr``."""
    from repro.core import algorithms, placement_service, setcover

    if name in ("fit_control", "unchanged_state"):
        mp.setattr(placement_service, "ALGORITHMS", dict(
            algorithms.ALGORITHMS,
            lmbr=functools.partial(algorithms.lmbr, max_moves=0)))
    elif name == "altered_spans":
        mp.setattr(setcover, "batched_spans_csr",
                   _altered_spans(setcover.batched_spans_csr))
    elif name == "half_trace":
        PS = placement_service.PlacementService
        mp.setattr(PS, "fit", _half_trace(PS.fit))
    else:
        raise KeyError(f"no control or fault {name!r}")


FIT_FAULTS = ("unchanged_state", "altered_spans", "half_trace")
