"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

- device busy time: the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:`` plane),
  clipped to the window, averaged over the devices;
- device time per program: the ``XLA Modules`` events grouped by the
  jitted function's name (``jit_pallas_spatial(12)`` → ``pallas_spatial``);
- the breakdown: the device operations that took most time, and the
  longest idle gaps labelled by the innermost benchmark host span
  (``chipbench.*``) that covers the gap's middle.

Events are reduced from plain tuples ``(plane, line, name, start_ns,
dur_ns)`` so that the arithmetic can be checked without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
_PROGRAM = re.compile(r"^(?:jit_)?([A-Za-z0-9_.\-]+?)(?:\(\d+\))?$")


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load_events(path: str) -> List[Event]:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def program_name(module_event: str) -> str:
    m = _PROGRAM.match(module_event.strip())
    return m.group(1) if m else module_event


def op_name(op_event: str) -> str:
    """``%pallas_spatial.1 = (f32[...]) custom-call(...)`` → the op's own
    name, ``pallas_spatial.1``."""
    return op_event.split(" = ", 1)[0].strip().lstrip("%")


def merged(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_events(events: Sequence[Event],
                  window: Optional[Tuple[float, float]] = None,
                  top: int = 10) -> Dict[str, object]:
    """Busy and idle time, device time per program and the breakdown.
    ``window`` is (start_ns, end_ns); by default the ``chipbench.window``
    host span, else the extent of the device events."""
    dev_ops: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    dev_mods: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    spans: List[Tuple[float, float, str]] = []
    for plane, line, name, start, dur in events:
        if plane.startswith("/device:"):
            if line == OPS_LINE:
                dev_ops[plane].append((start, start + dur, name))
            elif line == MODULES_LINE:
                dev_mods[plane].append((start, start + dur, name))
        elif name.startswith(SPAN_PREFIX):
            spans.append((start, start + dur, name[len(SPAN_PREFIX):]))
    # A device plane without an ops line counts its modules as busy.
    for plane, mods in dev_mods.items():
        if plane not in dev_ops:
            dev_ops[plane] = list(mods)
    if window is None:
        win = [(s, e) for s, e, n in spans if n == "window"]
        if win:
            window = win[0]
        else:
            allv = [iv for ops in dev_ops.values() for iv in ops]
            window = (min(s for s, _, _ in allv),
                      max(e for _, e, _ in allv)) if allv else (0.0, 0.0)
    lo, hi = window
    window_s = (hi - lo) / 1e9
    planes = sorted(dev_ops)
    busy = []
    op_time: Dict[str, float] = defaultdict(float)
    prog_time: Dict[str, float] = defaultdict(float)
    prog_calls: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for plane in planes:
        ivs = []
        for s, e, name in dev_ops[plane]:
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            op_time[op_name(name)] += (c[1] - c[0]) / 1e9
        m = merged(ivs)
        busy.append(sum(e - s for s, e in m) / 1e9)
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for s, e, name in dev_mods.get(plane, ()):
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            prog = program_name(name)
            prog_time[prog] += (c[1] - c[0]) / 1e9
            prog_calls[prog] += 1
    n_dev = max(len(planes), 1)
    busy_s = sum(busy) / n_dev
    inner = [sp for sp in spans if sp[2] != "window"]

    def label(s: float, e: float) -> str:
        mid = (s + e) / 2
        cover = [sp for sp in inner if sp[0] <= mid <= sp[1]]
        if not cover:
            return "host:outside-spans"
        return "host:" + min(cover, key=lambda sp: sp[1] - sp[0])[2]

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": len(planes),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "programs": {k: prog_time[k] / n_dev for k in prog_time},
        "program_calls": {k: prog_calls[k] / n_dev for k in prog_calls},
        "breakdown": {
            "device_ops": [[k, v / n_dev] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[label(s, e), (e - s) / 1e9]
                          for s, e in gaps[:top]],
        },
    }


def reduce_dir(log_dir: str) -> Optional[Dict[str, object]]:
    path = find_xplane(log_dir)
    if path is None:
        return None
    return reduce_events(load_events(path))


def program_seconds(summary: Optional[dict], names: Sequence[str]
                    ) -> Optional[float]:
    """Device seconds of the named programs, or None when none ran."""
    if not summary:
        return None
    progs = summary["programs"]
    hit = [progs[n] for n in names if n in progs]
    return float(sum(hit)) if hit else None
