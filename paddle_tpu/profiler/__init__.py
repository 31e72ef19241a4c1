"""``paddle.profiler`` over the XLA/xprof stack.

Reference: ``python/paddle/profiler/`` + C++ host/CUPTI tracers
(SURVEY.md §5.1). On TPU, libtpu/XLA already emit the device timeline
(xplane); this module wraps ``jax.profiler`` with the reference's API shape:
``Profiler(targets, scheduler)``, ``RecordEvent``, chrome-trace export
(TensorBoard 'trace viewer' via the xplane dump directory).
"""

from __future__ import annotations

import contextlib
import enum
import os
import time
from typing import Callable, Iterable, Optional, Tuple, Union

import jax

__all__ = ["ProfilerTarget", "ProfilerState", "Profiler", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result", "SummaryView"]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int], ProfilerState]:
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


class Profiler:
    def __init__(self, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler: Union[Callable, Tuple[int, int], None] = None,
                 on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False,
                 log_dir: Optional[str] = None):
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=start, ready=0, record=end - start,
                                       repeat=1)
        self._scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        self._on_trace_ready = on_trace_ready
        self._log_dir = log_dir or os.path.join(os.getcwd(), "profiler_log")
        self._step = 0
        self._running = False
        self._timer_only = timer_only
        self._step_times = []
        self._last = None
        # host spans: op dispatch + RecordEvent ranges, collected via
        # profiler._hooks while this profiler is recording
        self._host_ops = {}     # name -> [calls, total_ns]
        self._host_spans = []   # (name, kind, start_ns, dur_ns)

    def _host_event(self, name, start_ns, end_ns, kind):
        a = self._host_ops.setdefault(name, [0, 0.0])
        a[0] += 1
        a[1] += end_ns - start_ns
        if len(self._host_spans) < 200_000:  # bound trace memory
            self._host_spans.append((name, kind, start_ns, end_ns - start_ns))

    def start(self):
        from . import _hooks

        self._state = self._scheduler(self._step)
        recording = self._state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        if recording and not self._timer_only:
            self._start_trace()
        # host spans track the RECORD windows only, matching the device
        # trace (timer_only profilers have no device trace — collect
        # whenever the scheduler says record)
        if recording and self not in _hooks.COLLECTORS:
            _hooks.COLLECTORS.append(self)
        self._last = time.perf_counter()
        return self

    def _start_trace(self):
        """Open the jax trace and leave one span in it that carries its
        own ``perf_counter_ns`` start: the measured offset by which
        ``export_chrome_tracing`` places spans stamped on that clock."""
        from . import _hooks

        jax.profiler.start_trace(self._log_dir)
        self._running = True
        with _hooks.span("profiler.clock", "profiler",
                         pc_ns=_hooks.now_ns()):
            pass

    def stop(self):
        from . import _hooks

        if self in _hooks.COLLECTORS:
            _hooks.COLLECTORS.remove(self)
        if self._running:
            jax.profiler.stop_trace()
            self._running = False
            if self._on_trace_ready:
                self._on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last is not None:
            self._step_times.append(now - self._last)
        self._last = now
        self._step += 1
        new_state = self._scheduler(self._step)
        from . import _hooks

        # host-span collection follows the scheduler's record windows for
        # every profiler kind (timer_only included)
        recording = new_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN)
        if recording and self not in _hooks.COLLECTORS:
            _hooks.COLLECTORS.append(self)
        elif not recording and self in _hooks.COLLECTORS:
            _hooks.COLLECTORS.remove(self)
        if self._timer_only:
            return
        if self._running and new_state == ProfilerState.CLOSED:
            self.stop()
        elif not self._running and recording:
            self._start_trace()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None, scopes=None):
        """Reference-shaped summary tables (SURVEY §5.1): step overview,
        host operator view (dispatch spans + RecordEvent ranges), and —
        when an xplane trace was captured — the device op-level (XLA
        modules), kernel-level (HLO opcodes) and scope-level views with
        device occupancy. The scope view puts every device op under the
        ``jax.named_scope`` path of its ``op_name`` (``embed``, ``qkv``,
        ``kv_write``, ``attention``, ``post``, ``head``, ``sample`` under
        ``segment.admit`` / ``segment.decode``; ``loss`` with ``.bwd``
        halves, ``grad_clip``, ``optimizer``; a named Pallas kernel is a
        leaf). A TPU trace carries the names; where a backend's does not
        (CPU), pass ``scopes=_xplane.scope_map(compiled.as_text())``.
        "Device idle by host span" (DeviceView) answers why the chip
        waited: its idle time between programs under the innermost
        ``serving.*`` span open on the host (``_xplane.idle_by_span``):
        under ``serving.segment.fetch`` the device finished before the
        host was told, under ``.launch`` it was dispatched and had not
        started, under ``serving.segment`` itself code no phase names.
        ``views`` selects a subset (SummaryView values)."""
        from . import _xplane

        import numpy as np

        n = len(self._step_times)
        if n:
            ts = np.asarray(self._step_times) * 1000
            print(f"steps: {n}  avg: {ts.mean():.3f}ms  "
                  f"p50: {np.percentile(ts, 50):.3f}ms "
                  f"p99: {np.percentile(ts, 99):.3f}ms  "
                  f"trace dir: {self._log_dir}")
        else:
            print("No steps recorded.")

        want = None if views is None else {v for v in views}

        def wanted(v):
            return want is None or v in want

        if op_detail and self._host_ops and wanted(SummaryView.OperatorView):
            print(_xplane.format_table("Host operator view (eager dispatch)",
                                       self._host_ops))
        if self._running or self._timer_only:
            return
        tables, _ = _xplane.parse(self._log_dir, scopes=scopes)
        if tables is None:
            return
        if tables["modules"] and wanted(SummaryView.ModelView):
            occ = tables["occupancy"]
            dev = tables["device"] or "device"
            head = f"Device op view ({dev}"
            head += f", occupancy {occ:.1%})" if occ is not None else ")"
            print(_xplane.format_table(head, tables["modules"]))
        if tables["idle"] and wanted(SummaryView.DeviceView):
            print(_xplane.format_table("Device idle by host span",
                                       tables["idle"], width=40,
                                       count="gaps"))
        if tables["kernels"] and wanted(SummaryView.KernelView):
            print(_xplane.format_table("Device kernel view (HLO)",
                                       tables["kernels"]))
            print(_xplane.format_table("Device scope view (named_scope)",
                                       tables["scopes"], limit=40,
                                       width=46))

    def export_chrome_tracing(self, dir_name: Optional[str] = None,
                              worker_name: Optional[str] = None) -> str:
        """Write a loadable chrome-trace JSON (device xplane spans merged
        with the host dispatch/RecordEvent spans) and return its path —
        the reference's ``export_chrome_tracing`` artifact. The raw xplane
        protos stay under log_dir for TensorBoard's trace viewer."""
        import json

        from . import _xplane

        out_dir = dir_name or self._log_dir
        os.makedirs(out_dir, exist_ok=True)
        tables, events = _xplane.parse(self._log_dir)
        # host spans are stamped on perf_counter, xplane spans on the
        # trace's clock: place the former by the offset MEASURED on a
        # trace span that carries its own perf_counter start (this
        # profiler's ``profiler.clock``). With no xplane (timer_only)
        # there is one clock and nothing to align.
        offset = (tables or {}).get("clock_offset_ns") or 0
        for name, kind, start_ns, dur_ns in self._host_spans:
            events.append({
                "ph": "X", "name": name, "cat": kind,
                "pid": "host", "tid": f"host {kind}",
                "ts": (start_ns + offset) / 1e3, "dur": dur_ns / 1e3,
            })
        path = os.path.join(
            out_dir, f"{worker_name or 'worker'}.chrome_trace.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    export = export_chrome_tracing


class RecordEvent:
    """Named range in the device/host timeline (reference RAII RecordEvent):
    a ``_hooks.span`` of kind ``range`` — a ``TraceAnnotation`` on the
    xplane timeline plus a host span reported to any recording Profiler
    for its tables/chrome trace."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        from . import _hooks

        self._span = _hooks.span(self.name, kind="range")
        self._span.__enter__()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof: Profiler):
        return dir_name

    return handler


def load_profiler_result(filename: str):
    """Load an exported chrome trace (or a trace dir containing one) back
    as its event list (reference: ``load_profiler_result`` re-loads a
    saved profile for inspection)."""
    import glob as _glob
    import json as _json

    path = filename
    if os.path.isdir(path):
        hits = sorted(_glob.glob(os.path.join(path, "*.chrome_trace.json")),
                      key=os.path.getmtime)  # newest, not alphabetical
        if not hits:
            raise FileNotFoundError(
                f"no *.chrome_trace.json under {filename!r}; call "
                "Profiler.export_chrome_tracing() first (raw xplane "
                "protos are viewable in TensorBoard)")
        path = hits[-1]
    with open(path) as f:
        return _json.load(f)["traceEvents"]


class SummaryView(enum.Enum):
    """Summary table selector (reference ``paddle.profiler.SummaryView``)."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
