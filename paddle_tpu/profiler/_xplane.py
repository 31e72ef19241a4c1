"""xplane → summary tables / chrome trace (the device half of §5.1).

Reference counterpart: the CUPTI device tracer + chrome-trace serializer
(``paddle/fluid/platform/profiler/``): kernel/memcpy timelines and the
op/kernel summary tables. On TPU the device timeline already exists — XLA
emits xplane protos into the trace dir — so this module PARSES it
(``jax.profiler.ProfileData``) instead of re-collecting it:

* ``parse`` -> tables: per-plane aggregation of the "XLA Modules" line
  (program-level spans — the op-level view) and the "XLA Ops" line
  (HLO-instruction spans — the kernel-level view, and the per-SCOPE view:
  each op under the ``jax.named_scope`` path of its ``op_name``, PR 25),
  device occupancy (busy module time / observed wall), the measured
  offset between the trace's clock and ``perf_counter_ns``, and the
  device's idle time between programs put down to the program's
  ``serving.*`` host spans on the same clock (``idle_by_span``, PR 38).
* ``parse`` -> chrome events: the same spans as chrome-trace "X" events,
  merged with the profiler's host spans into one ``chrome_trace.json``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple


def latest_xplane(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _profile_data():
    """The xplane reader: jax's ProfileData binding when this jaxlib
    ships it, else the in-tree wire-format parser (same attribute
    surface; see _xplane_pb)."""
    try:
        from jax.profiler import ProfileData

        return ProfileData
    except ImportError:
        from ._xplane_pb import XSpaceData

        return XSpaceData


_HLO_RE = re.compile(r"=\s*\S+\s+([a-zA-Z][\w-]*)\(")

# The program's scope vocabulary: every ``jax.named_scope`` name in
# models/llama.py, inference/serving.py (segment programs) and optimizer/.
# The per-scope table keeps these components of an op's ``op_name`` path
# and drops jax's own (jit(..), while, body, cond, branch_N_fun, ...).
SCOPES = ("embed", "qkv", "kv_write", "attention", "attention_window",
          "attention_full", "post", "head", "sample",
          "latent_qkv", "router", "experts", "shared_expert", "dense_ffn",
          "retention_qkv", "gate", "retention", "ffn",
          "segment.admit", "segment.decode",
          "loss", "head_ce", "grad_clip", "optimizer")


def scope_key(op_name: str) -> str:
    """An op's scope from its HLO ``op_name`` metadata:
    ``jit(segment)/while/body/cond/branch_0_fun/segment.decode/while/body/
    closed_call/qkv/dot_general`` -> ``segment.decode/qkv``. A backward op
    (a ``transpose(..)`` component anywhere in the path) gets ``.bwd`` on
    its innermost scope: ``jit(train_step)/loss/transpose(jvp(post))/mul``
    -> ``loss/post.bwd``. A named Pallas kernel keeps its name as the
    leaf: ``.../qkv/fused_rms_norm/pallas_call`` ->
    ``segment.decode/qkv/fused_rms_norm``."""
    parts = op_name.split("/")
    out, bwd = [], False
    for part in parts:
        bwd = bwd or part.startswith("transpose(")
        inner = part.rstrip(")").rsplit("(", 1)[-1]
        if inner in SCOPES and inner not in out[-1:]:
            out.append(inner)
    if not out:
        return "(no scope)"
    if bwd:
        out[-1] += ".bwd"
    if len(parts) > 1 and parts[-1] == "pallas_call":
        out.append(parts[-2])
    return "/".join(out)


_META_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", re.M)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """instruction name -> scope, from a compiled program's ``as_text()``:
    the way to the per-scope table where the trace's op events carry no
    ``op_name`` of their own (``parse(..., scopes=scope_map(text))``)."""
    return {name: scope_key(op) for name, op in _META_RE.findall(hlo_text)}


def _kernel_key(event_name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion`` (HLO opcode); the
    result type may be a tuple: ``%while.2 = (s32[], f32[8]) while(..)``."""
    head, eq, rest = event_name.partition(" = ")
    if eq and rest.startswith("("):     # skip the tuple type to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = "x" + rest[i + 1:]
                break
    m = _HLO_RE.search("= " + rest) if eq else None
    if m:
        return m.group(1)
    return event_name.split(" ", 1)[0].lstrip("%")


def _module_key(name: str) -> str:
    """jit_matmul(12345...) -> jit_matmul."""
    return name.split("(", 1)[0]


# control-flow ops: their events span the ops of their bodies, which the
# line lists too — counting both would bill the same device time twice
_CONTAINERS = frozenset({"while", "conditional", "call"})

# the host spans the idle table reads, and its key for idle under none
IDLE_SPANS = "serving."
NO_SPAN = "(no span)"
# the clock check's anchors: a segment program starts after the launch
# span that dispatched it started, and ends before the fetch span that
# waited for it ended
SEGMENT_ANCHORS = ("jit_segment", "serving.segment.launch",
                   "serving.segment.fetch")


def device_offset(modules: List[Tuple[int, int, str]],
                  spans: List[Tuple[int, int, str]]) -> int:
    """ns to add to one device plane's times so that the host's and the
    device's clocks agree on every segment: each ``jit_segment`` starts
    after its ``launch`` span started and ends before its ``fetch`` span
    ended (a span is paired with the program nearest its end). 0 where
    they already agree, or where no shift satisfies both; else the least
    shift that does."""
    prog, launch, fetch = SEGMENT_ANCHORS
    runs = [(s, e) for s, e, n in modules if n == prog]
    if not runs:
        return 0
    starts = sorted(s for s, _ in runs)
    ends = sorted(e for _, e in runs)

    def nearest(xs, t):
        i = bisect.bisect_left(xs, t)
        return min(xs[max(0, i - 1):i + 1], key=lambda x: abs(x - t))

    lo = max((s - nearest(starts, e) for s, e, n in spans if n == launch),
             default=None)
    hi = min((e - nearest(ends, e) for s, e, n in spans if n == fetch),
             default=None)
    if lo is None or hi is None or lo > hi or lo <= 0 <= hi:
        return 0
    return lo if lo > 0 else hi


def idle_by_span(device_modules: Iterable[List[Tuple[int, int]]],
                 spans: List[Tuple[int, int, str]]
                 ) -> Dict[str, List[float]]:
    """The device's idle time put down to the host span that was open.

    ``device_modules``: per device plane, its ``XLA Modules`` events as
    (start_ns, end_ns); ``spans``: host spans as (start_ns, end_ns,
    name), on the same clock. Every interval between consecutive modules
    of a plane is split over the spans that overlap it, each part given
    to the INNERMOST span open there (the latest started: a phase wins
    over ``serving.segment``, which wins over nothing), a part under no
    span to ``NO_SPAN``. Returns {name: [gaps it has a part of, ns]},
    summed over planes."""
    spans = sorted(spans)
    starts = [sp[0] for sp in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    out: Dict[str, List[float]] = {}
    for modules in device_modules:
        hi = None
        for s, e in sorted(modules):
            if hi is not None and s > hi:
                for name, ns in _split_gap(hi, s, spans, starts,
                                           longest).items():
                    acc = out.setdefault(name, [0, 0.0])
                    acc[0] += 1
                    acc[1] += ns
            hi = e if hi is None else max(hi, e)
    return out


def _split_gap(a, b, spans, starts, longest) -> Dict[str, float]:
    """(a, b) split by the innermost span open in each part."""
    lo = bisect.bisect_left(starts, a - longest)
    cover = [sp for sp in spans[lo:bisect.bisect_left(starts, b)]
             if sp[1] > a]
    cuts = sorted({a, b} | {t for s, e, _ in cover for t in (s, e)
                            if a < t < b})
    parts: Dict[str, float] = {}
    for p, q in zip(cuts, cuts[1:]):
        open_ = [sp for sp in cover if sp[0] <= p and sp[1] >= q]
        name = (max(open_, key=lambda sp: (sp[0], -sp[1]))[2] if open_
                else NO_SPAN)
        parts[name] = parts.get(name, 0.0) + (q - p)
    return parts


def parse(log_dir: str, scopes: Optional[Dict[str, str]] = None):
    """Returns (tables, chrome_events) or (None, []) when no xplane exists.

    tables = {
      'modules': {name: [calls, total_ns]},
      'kernels': {opcode: [calls, total_ns]},
      'scopes':  {scope: [calls, total_ns]},   # see scope_key
      'occupancy': float | None,   # busy/wall over the device plane
      'device': plane name,
      'clock_offset_ns': int | None,  # trace clock - perf_counter_ns
      'idle': {span: [gaps, ns]},   # see idle_by_span
      'idle_offset_ns': [int],      # per device plane, device_offset
    }

    An op's scope comes from its ``op_name``: on a TPU trace the event
    metadata's ``tf_op`` stat (``_xplane_pb.op_names``); else ``scopes``
    (instruction name -> scope: ``scope_map`` of the compiled program's
    ``as_text()``); else ``(no scope)``. The CPU backend writes no
    ``XLA Ops`` line: there a host-thread event with an ``hlo_op`` stat
    is an op. ``clock_offset_ns`` is measured on a host-plane span that
    carries its own ``perf_counter_ns`` start as the stat ``pc_ns``
    (``serving.segment``, ``profiler.clock``). ``idle`` reads event
    names and times only, so the in-tree reader (no stats) gives it
    too."""
    path = latest_xplane(log_dir)
    if path is None:
        return None, []
    from ._xplane_pb import op_names

    pd = _profile_data().from_file(path)
    ops_meta = op_names(path)
    scopes = scopes or {}
    tables = {"modules": {}, "kernels": {}, "scopes": {}, "occupancy": None,
              "device": "", "clock_offset_ns": None}
    chrome: List[dict] = []
    occs: List[float] = []
    device_modules: List[List[Tuple[int, int, str]]] = []
    host_spans: List[Tuple[int, int, str]] = []

    def add(table: str, key: str, ns: float) -> None:
        # accumulate across planes (multi-chip: every device plane runs
        # the same modules — counts and times must SUM, not overwrite)
        cur = tables[table].setdefault(key, [0, 0.0])
        cur[0] += 1
        cur[1] += ns

    def add_op(plane, tid, ev, instr: str, opcode: str) -> None:
        if opcode in _CONTAINERS:   # its body's ops are events of their own
            return
        add("kernels", opcode, ev.duration_ns)
        op = ops_meta.get(ev.name)
        add("scopes", scope_key(op) if op
            else scopes.get(instr, "(no scope)"), ev.duration_ns)
        chrome.append({"ph": "X", "name": opcode, "cat": "XLA Ops",
                       "pid": plane.name, "tid": tid,
                       "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3})

    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    add_op(plane, line.name, ev,
                           ev.name.split(" ", 1)[0].lstrip("%"),
                           _kernel_key(ev.name))
            elif line.name == "XLA Modules":
                lo, hi, busy = None, None, 0.0
                if is_device:
                    device_modules.append(
                        [(ev.start_ns, ev.start_ns + ev.duration_ns,
                          _module_key(ev.name)) for ev in line.events])
                for ev in line.events:
                    key = _module_key(ev.name)
                    add("modules", key, ev.duration_ns)
                    end = ev.start_ns + ev.duration_ns
                    lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                    hi = end if hi is None else max(hi, end)
                    busy += ev.duration_ns
                    chrome.append({
                        "ph": "X", "name": key, "cat": line.name,
                        "pid": plane.name, "tid": line.name,
                        "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                    })
                if is_device and lo is not None:
                    if hi > lo:
                        occs.append(busy / (hi - lo))
                    tables["device"] = plane.name
            elif not is_device:
                for ev in line.events:
                    if ev.name.startswith(IDLE_SPANS):
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
                    # (the in-tree fallback reader's events have no stats)
                    st = dict(getattr(ev, "stats", None) or ())
                    if "hlo_op" in st:      # the CPU backend's op events
                        add_op(plane, line.name, ev, str(st["hlo_op"]),
                               str(st["hlo_op"]).split(".", 1)[0])
                    elif "pc_ns" in st and \
                            tables["clock_offset_ns"] is None:
                        tables["clock_offset_ns"] = (
                            int(ev.start_ns) - int(st["pc_ns"]))
    if occs:
        tables["occupancy"] = sum(occs) / len(occs)  # mean over planes
    # the device's idle, read against the host spans on ONE clock: each
    # plane shifted by what its segments say the two clocks disagree by
    offsets = [device_offset(m, host_spans) for m in device_modules]
    tables["idle_offset_ns"] = offsets
    tables["idle"] = idle_by_span(
        [[(s + d, e + d) for s, e, _ in m]
         for m, d in zip(device_modules, offsets)], host_spans)
    return tables, chrome


def format_table(title: str, rows: Dict[str, List[float]],
                 total_ns: Optional[float] = None, limit: int = 20,
                 width: int = 34, count: str = "calls") -> str:
    """name / calls / total / avg / share — the reference's summary shape."""
    if not rows:
        return ""
    total = total_ns or sum(v[1] for v in rows.values()) or 1.0
    out = [f"\n--- {title} " + "-" * max(1, 24 + width - len(title)),
           f"{'name':<{width}} {count:>6} {'total(ms)':>10} "
           f"{'avg(us)':>9} {'share':>6}"]
    for name, (calls, ns) in sorted(rows.items(),
                                    key=lambda kv: -kv[1][1])[:limit]:
        out.append(f"{name[:width]:<{width}} {calls:>6} {ns / 1e6:>10.3f} "
                   f"{ns / calls / 1e3:>9.1f} {ns / total:>6.1%}")
    return "\n".join(out)


def instr_profile(log_dir: str, n_steps: int = 1):
    """Aggregate per-HLO-instruction device time from the latest xplane in
    ``log_dir``: returns (agg, total_ns) with agg[name] = [calls, ns].
    Shared by the benchmark profilers (step/decode/resnet)."""
    path = latest_xplane(log_dir)
    assert path, f"no xplane in {log_dir}"
    pd = _profile_data().from_file(path)
    agg: Dict[str, List[float]] = {}
    total = 0.0
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.split(" ", 1)[0]
                a = agg.setdefault(name, [0, 0.0])
                a[0] += 1
                a[1] += ev.duration_ns
                total += ev.duration_ns
    return agg, total


def print_instr_profile(log_dir: str, n_steps: int, top_n: int,
                        header: str = "") -> None:
    agg, total = instr_profile(log_dir)
    print(f"{header}{len(agg)} distinct HLO instrs, "
          f"{total / 1e6 / n_steps:.1f} ms device time/step")
    print(f"{'instr':<58} {'calls':>6} {'ms/step':>8} {'share':>6}")
    for name, (c, ns) in sorted(agg.items(),
                                key=lambda kv: -kv[1][1])[:top_n]:
        print(f"{name[:58]:<58} {c:>6} {ns / 1e6 / n_steps:>8.3f} "
              f"{ns / total:>6.1%}")
