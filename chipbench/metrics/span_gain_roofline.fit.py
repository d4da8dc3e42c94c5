"""The ``span_gain`` kernel's share of its HBM roofline: the bytes each
call needs at its padded shapes, over the v5e's HBM bandwidth, over the
kernel's device time in the trace."""

from chipbench import readers


def read(run):
    return readers.kernel_roofline_pct(run, "span_gain")
