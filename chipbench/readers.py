"""Arithmetic shared by the metric readers in ``chipbench/metrics``."""

from __future__ import annotations

from .peaks import peaks
from .roofline import KERNELS


def idle_share_pct(run):
    """Share of the traced window in which no op ran on the device, %."""
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]


def kernel_roofline_pct(run, kernel: str):
    """The kernel's share of its HBM roofline over the traced window: the
    least time the chip could take for every call (the bytes the call
    needs over peak HBM bandwidth; popcounts on the vector unit are far
    from any compute bound), over the kernel's device time.  Nothing to
    read where it never ran."""
    if run.trace is None:
        return None
    k = KERNELS[kernel]
    names = [n for n in run.trace["ops"] if k.matches(n)]
    seconds = sum(run.trace["ops"][n] for n in names)
    if seconds <= 0:
        return None
    nbytes = sum(run.trace["calls"].get(n, 0) * k.bytes(k.shapes(n))
                 for n in names)
    return 100.0 * nbytes / peaks(run.device_kind)["hbm_bytes_per_s"] / seconds
