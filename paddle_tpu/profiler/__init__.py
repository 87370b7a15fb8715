"""Profiler — paddle.profiler-parity API over jax.profiler
(upstream: python/paddle/profiler/{profiler,profiler_statistic}.py; C++
tracers: paddle/fluid/platform/profiler/host_tracer.cc,
cuda_tracer.cc, chrometracinglogger.cc).

TPU-native mapping:
* HostTracer's RecordEvent instrumentation → :class:`RecordEvent`
  (host-side ring buffer for ``summary()``) + a
  ``jax.profiler.TraceAnnotation`` so the range shows up on the device
  timeline (the role NVTX ranges play for nsight);
* CudaTracer (CUPTI) → the XLA/TPU trace collected by
  ``jax.profiler.start_trace`` (XPlane → TensorBoard/Perfetto, the
  Chrome-trace export analog);
* the wait/warmup/active scheduler, ProfilerTarget and summary tables
  keep the reference API shape.

Telemetry bridge (framework/telemetry.py): this module's host events
and the runtime-telemetry tracer share ONE stream. Every
:class:`RecordEvent` range lands in the telemetry span ring whenever a
tracer is live — either because ``FLAGS_telemetry=trace``, or because
a profiler RECORD window armed it (``make_scheduler`` states gate
collection: outside a RECORD window, with the flag off, nothing is
recorded). :func:`export_chrome_tracing` exports that unified ring as
an actual Chrome-trace JSON file (RecordEvent ranges, scheduler
serving spans, jit.compile events — everything the ring holds), so
the stub stops being dead plumbing. ``summary()`` keeps reading the
legacy host-event store for its tables.
"""
from __future__ import annotations

import contextlib
import enum
import os
import threading
import time
from collections import defaultdict
from typing import Callable, Iterable, Optional

import jax

from ..framework import telemetry as _telemetry

# the one switch of the program's spans: while a jax.profiler session
# collects (this module's Profiler, or a bare jax.profiler.start_trace
# by whoever drives the process), telemetry.span() is live and writes
# each range into that session's trace as well. telemetry.py is
# jax-free by contract, so the probe and the annotation class are
# handed to it from here. is_enabled() reads a flag of the TraceMe
# recorder: no backend is touched, the chip is left alone.
_telemetry.install_session_probe(
    jax.profiler.TraceAnnotation.is_enabled,
    jax.profiler.TraceAnnotation)

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
    "SortedKeys", "SummaryView", "export_chrome_tracing",
    "export_protobuf", "make_scheduler",
]


class ProfilerState(enum.IntEnum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.IntEnum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SortedKeys(enum.IntEnum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.IntEnum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


# -- host event collection ---------------------------------------------------
# Backing store is the native lock-free ring in csrc/runtime.cc (the
# HostTracer analog) when built; Python list fallback otherwise.

_events_lock = threading.Lock()
_events = []  # (name, start_s, dur_s)
_collecting = False


def _native_lib():
    from .. import csrc

    return csrc.get_lib()


def _record_event(name, t0, dur):
    lib = _native_lib()
    if lib is not None:
        lib.pt_events_record(name.encode()[:55], t0, dur)
    else:
        with _events_lock:
            _events.append((name, t0, dur))


def _drain_events():
    lib = _native_lib()
    if lib is not None:
        import ctypes

        from ..csrc import NativeEvent

        n = min(int(lib.pt_events_count()), 1 << 16)
        buf = (NativeEvent * max(n, 1))()
        got = lib.pt_events_snapshot(
            ctypes.cast(buf, ctypes.c_void_p), max(n, 1)
        )
        return [
            (buf[i].name.decode(errors="replace"), buf[i].t0, buf[i].dur)
            for i in range(got)
        ]
    with _events_lock:
        return list(_events)


def _clear_events():
    lib = _native_lib()
    if lib is not None:
        lib.pt_events_clear()
    with _events_lock:
        _events.clear()


class RecordEvent:
    """Host-side instrumentation range (upstream: RecordEvent in
    paddle/fluid/platform/profiler/event_tracing.h; Python
    paddle.profiler.RecordEvent). Also emits a TraceAnnotation so the
    range appears in the device trace."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self._t0 = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        if self._ann is None:
            return
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        self._ann = None
        if _collecting:
            _record_event(self.name, self._t0, dur)
        # telemetry bridge: the range also lands in the unified span
        # ring — present when FLAGS_telemetry=trace OR while a
        # profiler RECORD window has the tracer armed (None otherwise:
        # make_scheduler's CLOSED/READY states really collect nothing)
        tr = _telemetry.tracer()
        if tr is not None:
            tr.add_complete(self.name, self._t0, dur, cat="profiler")

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def _start_collecting():
    global _collecting
    _clear_events()
    # arm the telemetry tracer for the window: an explicit Profiler
    # RECORD state collects spans even with FLAGS_telemetry=off (the
    # user asked for a trace), and releases at window close. When the
    # profiler is what drives collection (flag not 'trace'), the ring
    # restarts per window — matching _clear_events, so each window's
    # chrome export holds ONLY that window. A trace-mode application
    # ring is the user's; never wipe it.
    tr = _telemetry.arm_tracer()
    if tr is not None and _telemetry.telemetry_mode() != "trace":
        tr.clear()
    _collecting = True


def _stop_collecting():
    global _collecting
    _collecting = False
    _telemetry.disarm_tracer()


# -- scheduler ---------------------------------------------------------------


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-state schedule (upstream: paddle.profiler.make_scheduler):
    skip_first steps CLOSED, then cycles of [closed CLOSED, ready READY,
    record RECORD(last=RECORD_AND_RETURN)], `repeat` times (0=forever).
    """
    assert closed >= 0 and ready >= 0 and record > 0
    cycle = closed + ready + record

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


# -- trace export callables --------------------------------------------------


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callable: writes the unified telemetry span ring
    (RecordEvent ranges + any serving/compile spans collected in the
    window, plus one named LANE per serving request when the
    request-trace book collected any — telemetry.RequestTraceBook)
    as a real Chrome-trace JSON file under ``dir_name`` — loadable in
    chrome://tracing / Perfetto. The XPlane trace XLA collects
    (non-timer_only runs) lands in the same directory for
    TensorBoard."""

    def handle(prof):
        worker = worker_name or f"worker_{os.getpid()}"
        try:
            os.makedirs(dir_name, exist_ok=True)
            path = _telemetry.export_chrome(
                os.path.join(dir_name, f"{worker}.chrome_trace.json"))
        except OSError:
            path = None
        if path is not None:
            prof._exported_to = prof._exported_to or path

    handle._dir = dir_name
    return handle


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


# -- Profiler ----------------------------------------------------------------


class Profiler:
    """paddle.profiler.Profiler-parity driver.

    with Profiler(scheduler=(2, 5)) as p:
        for step in range(...):
            train_step()
            p.step()
    p.summary()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None):
        self.timer_only = timer_only
        if isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self.scheduler = make_scheduler(
                closed=max(start - 1, 0),
                ready=1 if start > 0 else 0,
                record=end - start, repeat=1,
            )
        elif callable(scheduler):
            self.scheduler = scheduler
        else:
            self.scheduler = _default_state_scheduler
        self.on_trace_ready = on_trace_ready
        self._dir = getattr(on_trace_ready, "_dir", None) or os.path.join(
            os.getcwd(), "profiler_log"
        )
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._tracing = False
        self._exported_to = None
        self._step_t0 = None
        self._step_times = []

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self.current_state = self.scheduler(self.step_num)
        self._transit(ProfilerState.CLOSED, self.current_state)
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        self._transit(self.current_state, ProfilerState.CLOSED)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        if self._step_t0 is not None:
            dt = time.perf_counter() - self._step_t0
            self._step_times.append(
                (dt, num_samples) if num_samples else (dt, None)
            )
        self._step_t0 = time.perf_counter()
        prev = self.current_state
        self.step_num += 1
        self.current_state = self.scheduler(self.step_num)
        self._transit(prev, self.current_state)

    def _transit(self, prev: ProfilerState, new: ProfilerState):
        was_on = prev in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
        )
        now_on = new in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
        )
        if prev == ProfilerState.RECORD_AND_RETURN and now_on:
            # cycle boundary between adjacent record windows: close the
            # current trace (firing on_trace_ready) and open a new one
            self._transit(prev, ProfilerState.CLOSED)
            was_on = False
        if not was_on and now_on:
            _start_collecting()
            if not self.timer_only:
                try:
                    os.makedirs(self._dir, exist_ok=True)
                    jax.profiler.start_trace(self._dir)
                    self._tracing = True
                except Exception:
                    self._tracing = False
        elif was_on and not now_on:
            if self._tracing:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._tracing = False
                self._exported_to = self._dir
            _stop_collecting()
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting ---------------------------------------------------------
    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms", views=None):
        """Print an operator-level stats table from the host events
        (upstream: profiler_statistic.py summary tables)."""
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        ev = _drain_events()
        stats = defaultdict(lambda: [0, 0.0, 0.0])  # name -> [n, tot, mx]
        for name, _, dur in ev:
            s = stats[name]
            s[0] += 1
            s[1] += dur
            s[2] = max(s[2], dur)
        lines = [
            "-" * 75,
            f"{'Name':<35}{'Calls':>8}{'Total(' + time_unit + ')':>12}"
            f"{'Avg(' + time_unit + ')':>10}{'Max(' + time_unit + ')':>10}",
            "-" * 75,
        ]
        for name, (n, tot, mx) in sorted(
            stats.items(), key=lambda kv: -kv[1][1]
        ):
            lines.append(
                f"{name[:34]:<35}{n:>8}{tot * unit:>12.3f}"
                f"{tot / n * unit:>10.3f}{mx * unit:>10.3f}"
            )
        if self._step_times:
            tot = sum(t for t, _ in self._step_times)
            lines.append("-" * 75)
            lines.append(
                f"{'[steps]':<35}{len(self._step_times):>8}"
                f"{tot * unit:>12.3f}"
                f"{tot / len(self._step_times) * unit:>10.3f}"
                f"{max(t for t, _ in self._step_times) * unit:>10.3f}"
            )
            samples = [n for _, n in self._step_times if n]
            if samples:
                ips = sum(samples) / tot
                lines.append(f"{'[throughput/s]':<35}{ips:>20.2f}")
        if self._exported_to:
            lines.append(f"trace exported to: {self._exported_to}")
        lines.append("-" * 75)
        text = "\n".join(lines)
        print(text)
        return text


@contextlib.contextmanager
def profile(**kwargs):
    p = Profiler(**kwargs).start()
    try:
        yield p
    finally:
        p.stop()


def start_profiler(log_dir="profiler_log"):
    """Low-level: begin an XLA trace now (jax.profiler.start_trace)."""
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)


def stop_profiler():
    jax.profiler.stop_trace()
