"""Bytes each kernel call needs, from its padded shapes.

A kernel is found in the trace by a stable part of its op name, and a
call's shapes come from the op name, which on a TPU is the op's HLO
instruction (see ``trace_reduce``)."""

from __future__ import annotations

import dataclasses
import re
from typing import Callable


def span_gain_bytes(a: int, w2: int, n: int) -> int:
    """HBM bytes one ``span_gain`` call moves at padded shape (A, W2, N):
    the uint32 codes (A, W2, N) and remaining masks (A, W2) read once and
    the int32 gains (A, N) written once."""
    return 4 * (a * w2 * n + a * w2 + a * n)


@dataclasses.dataclass(frozen=True)
class Kernel:
    pattern: str                                # op names of its calls
    shapes: Callable[[str], tuple]              # op name -> padded shape
    bytes: Callable[..., int]

    def matches(self, op_name: str) -> bool:
        return re.search(self.pattern, op_name) is not None


def _codes_shape(hlo: str) -> tuple:
    """(A, W2, N) of the first rank-3 u32 operand in an op's HLO text."""
    m = re.search(r"custom-call\(u32\[(\d+),(\d+),(\d+)\]", hlo)
    if m is None:
        raise ValueError(f"no (A, W2, N) codes operand in {hlo[:200]!r}")
    return tuple(int(x) for x in m.groups())


KERNELS = {
    "span_gain": Kernel(r"^%span_gain[.\d]* = .*custom-call", _codes_shape,
                        lambda shape: span_gain_bytes(*shape)),
}
