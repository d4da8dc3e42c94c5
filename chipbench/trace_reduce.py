"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

A trace is first flattened (`load_xplane`) into two lists that hold only
what the reduction reads, so a small recorded trace can be kept as JSON:

* ``device``: ``[device_index, op_name, start_ns, dur_ns]`` for every
  event on a TPU plane's ``XLA Ops`` line.  On a TPU an op's name is its
  HLO instruction, operand shapes included (``%span_gain.1 =
  s32[4096,128]{...} custom-call(u32[4096,2,128]{...} ...)``);
* ``host``: ``[name, start_ns, dur_ns]`` for every host annotation the
  harness or a loop wrote (``Run.annotate``: named ``HOST_PREFIX`` +
  name, recorded without the prefix), on any host thread.

`reduce` then takes the measured window from the ``window`` annotation
and returns:

* ``busy_s``: the union of the device-op intervals inside the window,
  averaged over the devices that ran an op; ``idle_share`` is
  1 - busy / window;
* ``ops``: seconds of device time per op name, and ``calls``: how many
  calls of each op began in the window (a kernel is found by a stable
  part of its name);
* ``idle``: seconds of device idle time per enclosing host annotation,
  where time under no annotation counts as ``harness``.  Annotations
  inside the window do not nest, so no idle time counts twice.
"""

from __future__ import annotations

import glob
import os

import numpy as np

HOST_PREFIX = "chipbench."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
OUTSIDE = "harness"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load_xplane(path: str) -> dict:
    """Flatten one ``.xplane.pb`` into ``{"device": [...], "host": [...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = [], []
    dev_index: dict[str, int] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            if not plane.name.startswith("/device:TPU:"):
                continue
            idx = dev_index.setdefault(plane.name, len(dev_index))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    device.append([idx, ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name[len(HOST_PREFIX):],
                                     int(ev.start_ns), int(ev.duration_ns)])
    return {"device": device, "host": host}


def merge(intervals) -> np.ndarray:
    """Union of ``(start, end)`` intervals as sorted disjoint rows."""
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not len(iv):
        return iv
    iv = np.stack([iv[:, 0].clip(lo, hi), iv[:, 1].clip(lo, hi)], axis=1)
    return iv[iv[:, 1] > iv[:, 0]]


def _gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Complement of the disjoint sorted ``busy`` rows inside [lo, hi]."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]])
    g = edges.reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total overlap of two lists of disjoint sorted intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i, 0], b[j, 0])
        e = min(a[i, 1], b[j, 1])
        if e > s:
            total += e - s
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: dict) -> dict:
    """Window, busy and idle time, device time per op and idle time per
    host annotation, all in seconds."""
    wins = [(s, s + d) for name, s, d in trace["host"] if name == "window"]
    if len(wins) != 1:
        raise ValueError(f"want one 'window' annotation, found {len(wins)}")
    lo, hi = wins[0]
    devices = sorted({d[0] for d in trace["device"]})
    busy_per_dev, ops = [], {}
    busy0 = np.zeros((0, 2))
    for dev in devices:
        rows = [(s, s + d) for i, _, s, d in trace["device"] if i == dev]
        busy = _clip(merge(rows), lo, hi)
        busy_per_dev.append(float((busy[:, 1] - busy[:, 0]).sum()))
        if dev == devices[0]:
            busy0 = busy
    calls: dict[str, int] = {}
    for _, name, s, d in trace["device"]:
        e = min(s + d, hi)
        if e > max(s, lo):
            ops[name] = ops.get(name, 0.0) + (e - max(s, lo)) * 1e-9
        if lo <= s < hi:
            calls[name] = calls.get(name, 0) + 1
    window_ns = hi - lo
    busy_ns = float(np.mean(busy_per_dev)) if devices else 0.0
    gaps = _gaps(busy0, lo, hi)
    idle: dict[str, float] = {}
    covered = 0.0
    for name in sorted({n for n, _, _ in trace["host"]} - {"window"}):
        rows = [(s, s + d) for n, s, d in trace["host"] if n == name]
        ov = _overlap(gaps, _clip(merge(rows), lo, hi))
        if ov > 0:
            idle[name] = ov * 1e-9
            covered += ov
    total_idle = float((gaps[:, 1] - gaps[:, 0]).sum()) if len(gaps) else 0.0
    if total_idle - covered > 0:
        idle[OUTSIDE] = (total_idle - covered) * 1e-9
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "ops": ops,
        "calls": calls,
        "idle": idle,
        "longest_gap_s": float((gaps[:, 1] - gaps[:, 0]).max()) * 1e-9
        if len(gaps) else 0.0,
    }


def top(d: dict, k: int = 10) -> list:
    """The ``k`` largest entries of ``{name: seconds}`` as ``[name, s]``."""
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]
