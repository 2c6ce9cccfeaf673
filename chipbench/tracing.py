"""Reduction of a profiler trace to device busy time, idle gaps and the
operations that took the time; and the table of one chip's peaks.

A traced run records the measured window with ``jax.profiler`` and marks
it with a host span named ``WINDOW``.  Busy time is the union of the
intervals in which an operation ran on a device (the ``XLA Ops`` line of
each ``/device:TPU:<i>`` plane), clipped to the window and averaged over
the chips; the idle share is 1 - busy / window.  Operations are ranked
by self time (a loop's event less its body's), and each idle gap is named
by the shortest host event that covers at least half of it.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
TPU_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"

# One chip's published peaks, keyed by ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 394 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "int8_op_s": 394e12,
                    "hbm_bytes": 16e9, "hbm_byte_s": 819e9,
                    "ici_byte_s": 1600e9 / 8},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of this kind; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       "add them, with their source, to chipbench/tracing.py")
    return PEAKS[device_kind]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(plane, line_re: Optional[str] = None):
    for line in plane.lines:
        if line_re is None or re.search(line_re, line.name):
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce_trace(path: str, device_plane: str = TPU_PLANE,
                 ops_line: str = OPS_LINE, host_plane: str = HOST_PLANE,
                 top: int = 10) -> Optional[dict]:
    """Busy and window seconds, the operations that took most device time
    and the longest idle gaps, from one ``.xplane.pb``; None when the
    trace holds no window span or no device operation inside it."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    planes = list(prof.planes)
    for p in planes:
        lines = ", ".join(f"{ln.name} ({sum(1 for _ in ln.events)})"
                          for ln in p.lines)
        print(f"[chipbench] trace plane {p.name}: {lines[:400]}",
              file=sys.stderr)
    hosts = [p for p in planes if re.search(host_plane, p.name)]
    spans = [(s, e) for p in hosts for name, s, e in _events(p)
             if name == WINDOW]
    devices = [p for p in planes if re.search(device_plane, p.name)]
    if not spans or not devices:
        return None
    w0, w1 = spans[0]
    busy, by_op = [], defaultdict(float)
    first_union = None
    for plane in devices:
        ivs = [(max(s, w0), min(e, w1), name)
               for name, s, e in _events(plane, ops_line)
               if min(e, w1) > max(s, w0)]
        for name, sec in _self_times(ivs):
            by_op[name] += sec
        union = _union([(s, e) for s, e, _ in ivs])
        if first_union is None:
            first_union = union
        busy.append(sum(e - s for s, e in union) / 1e9)
    if not any(busy):
        return None
    chips = len(devices)
    gaps, prev = [], w0
    for s, e in first_union + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host_events = [(name, s, e) for p in hosts for name, s, e in _events(p)
                   if e > s and name != WINDOW]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / chips,
        "window_s": (w1 - w0) / 1e9,
        "chips": chips,
        "device_ops": [[name, sec / chips] for name, sec in ops],
        "idle_gaps": [[_name_gap(g, host_events), (g[1] - g[0]) / 1e9]
                      for g in gaps],
    }


def _self_times(ivs):
    """(short name, seconds) of each operation less the operations nested
    in it, as a loop's body ops are in the loop's event."""
    out, stack = [], []          # stack: [end, index into out]
    for s, e, name in sorted(ivs, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= (min(e, stack[-1][0]) - s) / 1e9
        short = name.split(" = ")[0].lstrip("%")
        out.append([short, (e - s) / 1e9])
        stack.append([e, len(out) - 1])
    return out


def _name_gap(gap, host_events) -> str:
    """The shortest host event covering at least half the gap, else the
    one that covers most of it."""
    g0, g1 = gap
    best, key = "host: no event", None
    for name, s, e in host_events:
        overlap = min(e, g1) - max(s, g0)
        if overlap <= 0:
            continue
        k = (0, e - s) if 2 * overlap >= g1 - g0 else (1, -overlap)
        if key is None or k < key:
            best, key = f"host: {name[:120]}", k
    return best
