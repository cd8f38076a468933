"""paddle.profiler analog.

ref: python/paddle/profiler/profiler.py:344 Profiler (scheduler windows,
RecordEvent spans, chrome-trace export), timer.py benchmark.

TPU-native backing: jax.profiler (XPlane/perfetto traces + TraceAnnotation
spans) replaces the reference's CUPTI tracer (SURVEY §5.1).
"""
import collections
import contextlib
import json
import os
import time

import jax

from . import timer as _timer_mod
from .timer import Benchmark, benchmark
from . import statistic as _statistic
from .statistic import (StatisticCollector, merge_statistics,
                        render_summary)


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    CUSTOM_DEVICE = "custom"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        total = closed + ready + record
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready factory: write the span timeline as chrome-trace
    JSON into dir_name/<worker>.json when the profiler stops. (Bit-rot
    fix: this used to only record the directory on the profiler object
    and nothing ever consumed it — the export path had no consumer
    until the serving telemetry plane landed.)"""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        prof._export_dir = dir_name
        prof.export(os.path.join(dir_name, f"{name}.json"))
    return handler


# The model phases of a step program (docs/observability.md "Device
# phases"): a closed vocabulary, opened in the program as `with
# phase(name):` at the seams every op chain shares. A `jax.named_scope`
# is metadata of the traced operations, nothing at run time; any
# `jax.profiler` trace shows it in each device operation's name stack,
# and perf/harness/phase_times.py splits a program's device time by it.
PHASES = ("embed", "attn_proj", "kv_write", "attend", "ffn", "head",
          "loss", "grad_sync", "optimizer")


def phase(name):
    """`jax.named_scope(name)` for a name of `PHASES`; ValueError for any
    other, so a trace never holds a phase no reader knows."""
    if name not in PHASES:
        raise ValueError(f"no model phase {name!r} (have {PHASES})")
    return jax.named_scope(name)


def span_totals():
    """{name: (count, seconds)} over every RecordEvent span ended in this
    process so far: cumulative, never reset, there whether or not any
    profiler runs. Counted at the span's own boundary, so a ratio of two
    entries is measured where the work happens. A plain dict updated
    under the GIL: exact on one thread, good enough across a router's."""
    return {name: (t[0], t[1]) for name, t in list(_TOTALS.items())}


def record_counters(source, values):
    """Keep one timestamped sample of a component's cumulative counters
    ({name: number}) beside span_totals(): always on, no profiler
    needed, bounded (the newest 4096 samples). The serving engine's
    health() samples its routing and page-group counters here, so a
    reader that knows two moments (a benchmark window's ends) takes the
    difference of the samples nearest them; see counter_history()."""
    _COUNTERS.append((time.monotonic(), source, dict(values)))


def counter_history(source=None):
    """[(time.monotonic() stamp, {name: cumulative value})] of every
    sample record_counters() kept (of `source`, if given), oldest
    first."""
    return [(t, v) for t, s, v in list(_COUNTERS)
            if source is None or s == source]


class RecordEvent(contextlib.ContextDecorator):
    """Span annotation, context manager or decorator as the reference's
    is (ref: profiler/utils.py RecordEvent); lowers to
    jax.profiler.TraceAnnotation, so the span lands on the host plane of
    ANY active jax.profiler session, on the device lines' clock. Always
    on: with no session a span costs about a microsecond, and its count
    and seconds still reach span_totals(). `stats` become the
    annotation's metadata (RecordEvent("cb.step", step=7))."""

    def __init__(self, name, event_type=None, **stats):
        self.name = name
        self._stats = stats
        self._ann = None
        self.begin_ts = None
        self.end_ts = None

    def _recreate_cm(self):
        # as a decorator: a span of its own per call, so calls on two
        # threads (or a call inside a call) do not share begin_ts
        return RecordEvent(self.name, **self._stats)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def begin(self):
        self.begin_ts = time.perf_counter()
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name,
                                                     **self._stats)
            self._ann.__enter__()
        except Exception:
            self._ann = None    # span timing still records host-side

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.begin_ts is None:
            return              # end() without begin(): nothing to record
        self.end_ts = time.perf_counter()
        total = _TOTALS.get(self.name)
        if total is None:
            total = _TOTALS[self.name] = [0, 0.0]
        total[0] += 1
        total[1] += self.end_ts - self.begin_ts
        _EVENTS.append((self.name, self.begin_ts, self.end_ts))
        c = _statistic._collector()
        if c is not None:
            c.record_span(self.name, self.begin_ts, self.end_ts)


# name -> [count, seconds], read through span_totals()
_TOTALS = {}
# (stamp, source, {name: value}) samples, read through counter_history()
_COUNTERS = collections.deque(maxlen=4096)

# span timeline consumed by Profiler.export — BOUNDED (a serving loop
# emits one span per dispatch; an unbounded list was a leak the moment
# the export path gained a consumer)
_EVENTS = collections.deque(maxlen=16384)


class Profiler:
    """ref: profiler/profiler.py:344."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self._scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        if isinstance(scheduler, tuple):
            start, end = scheduler
            self._scheduler = lambda step: (
                ProfilerState.RECORD if start <= step < end
                else ProfilerState.CLOSED)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._active = False
        self._export_dir = None
        self._logdir = os.environ.get("PADDLE_TPU_PROFILE_DIR",
                                      "/tmp/paddle_tpu_profile")
        # statistics tables (ref: profiler_statistic.py): a collector is
        # live only while this profiler records — per-op timing costs
        # nothing otherwise
        self.collector = StatisticCollector()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        self._state = self._scheduler(self._step)
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            # fresh session: the exported span timeline must hold THIS
            # session's spans, not a previous profiler's (the global
            # buffer outlives profiler objects; before the export path
            # had a consumer the stale carryover was invisible)
            _EVENTS.clear()
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN) \
                and not self._timer_only and not self._active:
            try:
                jax.profiler.start_trace(self._logdir)
                self._active = True
            except Exception:
                self._active = False
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            _statistic._set_collector(self.collector)
        benchmark().begin()

    def stop(self):
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
        if self._on_trace_ready:
            self._on_trace_ready(self)
        _statistic._set_collector(None)
        benchmark().end()

    def step(self, num_samples=None):
        self._step += 1
        new_state = self._scheduler(self._step)
        if new_state != self._state:
            if self._active and new_state == ProfilerState.CLOSED:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._active = False
            elif (not self._active
                  and new_state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
                  and not self._timer_only):
                try:
                    jax.profiler.start_trace(self._logdir)
                    self._active = True
                except Exception:
                    pass
            self._state = new_state
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            _statistic._set_collector(self.collector)
            self.collector.mark_step()
        else:
            _statistic._set_collector(None)
        benchmark().step(num_samples)

    def step_info(self, unit=None):
        return benchmark().step_info(unit)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Statistics tables (ref: profiler_statistic.py — op summary,
        span summary, memory summary)."""
        out = render_summary(self.collector, sorted_by=sorted_by)
        print(out)
        return out

    def export(self, path, format="json"):
        """Chrome-trace JSON of the RecordEvent span timeline —
        loadable in Perfetto / chrome://tracing next to the XPlane
        device trace jax.profiler wrote under the logdir."""
        events = [{"name": n, "ph": "X", "ts": b * 1e6,
                   "dur": max(0.0, (e - b) * 1e6), "pid": 0, "tid": 0}
                  for n, b, e in _EVENTS
                  if b is not None and e is not None]
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


from enum import Enum as _Enum  # noqa: E402


class SortedKeys(_Enum):
    """ref: profiler_statistic.py:49 SortedKeys — summary-table sort key.
    On TPU "GPU*" reads as accelerator/device time (the reference names
    are kept for API parity)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(_Enum):
    """ref: profiler.py:46 SummaryView."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    OperatorDetailView = 6
    MemoryView = 7
    MemoryManipulationView = 8
    UDFView = 9


def export_protobuf(dir_name, worker_name=None):
    """ref: profiler.py:270 export_protobuf — on_trace_ready factory.
    The TPU profile container IS protobuf: jax.profiler writes XPlane
    .pb/.xplane.pb files under <logdir>/plugins/profile/, so the handler
    collects those into dir_name/worker_name. When no device trace was
    captured (timer_only / trace unavailable), the span timeline is
    written as chrome-trace json instead — never silently nothing."""
    import shutil
    import socket

    def handler(prof):
        name = worker_name or f"{socket.gethostname()}_{os.getpid()}"
        target = os.path.join(dir_name, name)
        os.makedirs(target, exist_ok=True)
        copied = 0
        prof_dir = os.path.join(prof._logdir, "plugins", "profile")
        if os.path.isdir(prof_dir):
            for root, _dirs, files in os.walk(prof_dir):
                for fn in files:
                    if fn.endswith(".pb"):
                        shutil.copy2(os.path.join(root, fn),
                                     os.path.join(target, fn))
                        copied += 1
        if not copied:
            prof.export(os.path.join(target, "trace.json"))

    return handler
