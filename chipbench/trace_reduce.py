"""From a profiler trace (``*.xplane.pb``) to the numbers the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A device
plane is one whose name starts with ``/device:TPU:``; on it the line
``XLA Modules`` has one event per executed program and ``XLA Ops`` one per
executed HLO instruction (loop bodies repeat). All times are seconds.

``reduce(dir)`` returns None when the trace holds no device plane (a CPU
rehearsal), else::

  {"planes": n,
   "modules": {name: {"calls": c, "seconds": s}},   # summed over planes
   "ops":     {name: {"calls": c, "seconds": s}},
   "busy_s":  union of the op intervals, mean over planes,
   "span_s":  first op start to last op end, mean over planes,
   "gaps":    {"<module before> -> <module after>": {"count", "seconds",
               "longest"}}}  # device idle between programs, plane 0
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"


def latest_xplane(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def module_key(name: str) -> str:
    """``jit_segment(1234567)`` -> ``jit_segment``."""
    return name.split("(", 1)[0]


_HLO_RE = re.compile(r"^%?([\w.\-]+)")


def op_key(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``."""
    m = _HLO_RE.match(name)
    return m.group(1) if m else name


def union_seconds(intervals: List[Tuple[int, int]]) -> float:
    """Length of the union of [start, end) intervals given in ns."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _add(table: Dict[str, dict], key: str, ns: float) -> None:
    row = table.setdefault(key, {"calls": 0, "seconds": 0.0})
    row["calls"] += 1
    row["seconds"] += ns / 1e9


def reduce_planes(planes) -> Optional[dict]:
    """``planes``: objects with .name and .lines; a line has .name and
    .events; an event has .name, .start_ns and .duration_ns."""
    modules: Dict[str, dict] = {}
    ops: Dict[str, dict] = {}
    gaps: Dict[str, dict] = {}
    busy, span, n = [], [], 0
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        mod_iv, op_iv = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    _add(modules, module_key(ev.name), ev.duration_ns)
                    mod_iv.append((int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns),
                                   module_key(ev.name)))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    _add(ops, op_key(ev.name), ev.duration_ns)
                    op_iv.append((int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
        if not mod_iv and not op_iv:
            continue
        iv = op_iv or [(s, e) for s, e, _ in mod_iv]
        busy.append(union_seconds(iv))
        span.append((max(e for _, e in iv) - min(s for s, _ in iv)) / 1e9)
        if n == 0:
            mod_iv.sort()
            for (_, e0, a), (s1, _, b) in zip(mod_iv, mod_iv[1:]):
                if s1 > e0:
                    g = gaps.setdefault(f"{a} -> {b}", {
                        "count": 0, "seconds": 0.0, "longest": 0.0})
                    g["count"] += 1
                    g["seconds"] += (s1 - e0) / 1e9
                    g["longest"] = max(g["longest"], (s1 - e0) / 1e9)
        n += 1
    if n == 0:
        return None
    return {"planes": n, "modules": modules, "ops": ops,
            "busy_s": sum(busy) / n, "span_s": sum(span) / n, "gaps": gaps}


def reduce(log_dir: str) -> Optional[dict]:
    path = latest_xplane(log_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def module_seconds(trace: Optional[dict], names) -> Optional[float]:
    """Device seconds of the named programs (``"*"``: every program in the
    trace), per device plane; None when the trace has none of them."""
    if not trace:
        return None
    found = [row["seconds"] for n, row in trace["modules"].items()
             if n in names or "*" in names]
    if not found:
        return None
    return sum(found) / trace["planes"]


# ops that only hold other ops: their time is their contents' time
CONTAINERS = ("while", "cond", "conditional", "call", "closed_call")


def top(table: Dict[str, dict], k: int = 10) -> List[list]:
    """The ``k`` rows with most time, loops and branches left out."""
    rows = [(name, row) for name, row in table.items()
            if name.split(".")[0] not in CONTAINERS]
    rows.sort(key=lambda kv: -kv[1]["seconds"])
    return [[name, row["seconds"]] for name, row in rows[:k]]

