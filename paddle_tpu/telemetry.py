"""Unified runtime telemetry: the gate, step-correlated spans, and the
flight recorder (docs/observability.md).

The TensorFlow lineage treats timeline/metrics instrumentation as a
first-class subsystem (Abadi et al., arXiv:1605.08695 §5); this module
is that subsystem for paddle_tpu. It ties the two existing halves
together behind ONE switch:

- spans land in profiler.py as step-correlated chrome-trace events
  (named tracks: dispatch / feed-stage / drain / sync / compile,
  serving, and generation — the decode engine's prefill/decode-step
  spans ride the "generation" track), and
- latencies land in monitor.py timer histograms (TIMER_* names),

so one `FLAGS_telemetry=True` run yields both a timeline and
aggregates. The chrome timeline, the timers and the flight recorder
are OFF by default.

On the device trace (no flag). Every `span()` also opens a
`jax.profiler.TraceAnnotation` of the same name, so whenever a jax
profiler session is open — whoever opened it: the benchmark's traced
run, `profiler.start_device_trace`, an operator's
`jax.profiler.start_trace` — the program's spans lie in the same
`.xplane.pb` as the device's operations, on one clock. With
FLAGS_telemetry off a span is that annotation and nothing else: one
dict lookup and one small object, under a microsecond with no session
open. Hence the budget: at most 20 span entries per engine step and 6
per `TrainStep.__call__`, none inside a per-slot, per-token or
per-lane loop (tests/test_device_trace_names.py counts them).

ONE naming convention (docs/observability.md, "On the device trace"):
- host spans are `pt/<module>/<phase>` (`pt/engine/plan`,
  `pt/pool/wait`, `pt/trainstep/dispatch`); spans older than the
  convention keep `<module>/<phase>`; the benchmark's own start
  `bench/`;
- program scopes on the device (`jax.named_scope`, metadata of the
  compiled program, free at run time) are lower-case words for phases
  (`forward`, `optimizer`, `embed`, `qkv`, `kv_write`,
  `paged_attention`, `attn_out`, `mlp`, `unembed`, `sampler`,
  `kv_copy_on_write`, `flash_attention`, `layer_norm`, `attention`)
  and class names for layers
  (`BertModel`, `TransformerEncoderLayer`, `Linear`: nn/layer.py opens
  them). jax wraps the backward's operations in `transpose(jvp(...))`
  around the forward's names; no scope of the program's names them.

Step correlation: the executor (or any loop) enters `step_scope(n)`;
every span and FetchHandle created under it inherits step id `n`, so a
pipelined `train_from_dataset` trace shows dispatch N, feed-stage N+1,
and drain N−window as separate rows correlated by `args.step`.

Flight recorder: a bounded deque of the last FLAGS_telemetry_flight_steps
(default 64) step records — step id, program key, dispatch/drain
timestamps, fetch sync count. When a step raises, `attach_flight`
appends the dump to the exception notes, turning "NaN at some step"
into a reconstructable timeline.
"""
from __future__ import annotations

import re
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import monitor, profiler
from .flags import get_flag

__all__ = ["enabled", "span", "step_scope", "current_step",
           "trace_scope", "current_trace", "counter_sample",
           "note_device_program", "device_op_names",
           "flight_begin", "flight_note", "flight_records",
           "flight_dump", "flight_reset", "attach_flight"]

_tls = threading.local()


def enabled() -> bool:
    """The master gate (FLAGS_telemetry). Cheap: one dict lookup."""
    return bool(get_flag("FLAGS_telemetry"))


def now_us() -> float:
    return time.perf_counter() * 1e6


# ---------------------------------------------------------------------------
# step scope: thread-local current-step id
# ---------------------------------------------------------------------------

class _StepScope:
    __slots__ = ("_step", "_prev")

    def __init__(self, step: Optional[int]):
        self._step = step

    def __enter__(self):
        self._prev = getattr(_tls, "step", None)
        _tls.step = self._step
        return self

    def __exit__(self, *exc):
        _tls.step = self._prev
        return False


def step_scope(step: Optional[int]) -> _StepScope:
    """Bind `step` as the thread's current step id; spans and
    FetchHandles created inside inherit it."""
    return _StepScope(step)


def current_step() -> Optional[int]:
    return getattr(_tls, "step", None)


# ---------------------------------------------------------------------------
# trace scope: thread-local request-trace id(s)
# ---------------------------------------------------------------------------
# The request-tracing analog of step_scope (tracing.py owns the traces;
# this lives here so tracing can depend on telemetry without a cycle).
# The serving batcher / generation engine binds the batch's trace ids
# around execution; every span and FetchHandle created inside inherits
# them, so chrome-trace lanes and flight notes carry "which requests".

class _TraceScope:
    __slots__ = ("_tid", "_prev")

    def __init__(self, tid: str):
        self._tid = tid

    def __enter__(self):
        self._prev = getattr(_tls, "trace", None)
        _tls.trace = self._tid
        return self

    def __exit__(self, *exc):
        _tls.trace = self._prev
        return False


def trace_scope(tid: Optional[str]):
    """Bind `tid` (a trace id, or comma-joined ids for a coalesced
    batch) as the thread's current request trace. Falsy tid — tracing
    disabled, no real ids in the batch — is the shared no-op."""
    return _TraceScope(tid) if tid else _NOOP


def current_trace() -> Optional[str]:
    return getattr(_tls, "trace", None)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "step", "track", "cat", "timer", "trace",
                 "tid", "args", "_t0", "_ann")

    def __init__(self, name, step, track, cat, timer, trace, tid, args):
        self.name = name
        self.step = step
        self.track = track
        self.cat = cat
        self.timer = timer
        self.trace = trace
        self.tid = tid
        self.args = args

    def __enter__(self):
        # the same span on the profiler's clock, with the step and the
        # riders' trace ids for an operator's view
        kw = dict(self.args) if self.args else {}
        if self.step is not None:
            kw["step"] = self.step
        if self.tid is not None:
            kw["trace"] = self.tid
        self._ann = TraceAnnotation(self.name, **kw)
        self._ann.__enter__()
        self._t0 = now_us()
        return self

    def __exit__(self, *exc):
        t1 = now_us()
        self._ann.__exit__(*exc)
        dur = t1 - self._t0
        if self.trace:
            args = self.args
            if self.tid is not None:
                args = dict(args) if args else {}
                args["trace"] = self.tid
            profiler.add_trace_event(self.name, self._t0, dur,
                                     cat=self.cat, track=self.track,
                                     step=self.step, args=args)
        if self.timer:
            monitor.timer_observe(self.timer, dur)
        return False


def span(name: str, *, step: Optional[int] = None,
         track: Optional[str] = None, cat: str = "telemetry",
         timer: Optional[str] = None, trace: bool = True,
         args: Optional[Dict[str, Any]] = None):
    """Context manager around one region. Always a
    `jax.profiler.TraceAnnotation` of the same name: it lands in any
    open jax profiler session, beside the device's operations, and
    costs under a microsecond when none is open. With telemetry off it
    is that annotation and nothing else (no chrome event, no timer).
    With it on: `step=None` inherits the thread's step_scope; the
    thread's trace_scope ids (if any) land in the event's args.trace,
    correlating chrome-trace lanes with /tracez. `timer` additionally
    records the duration in the named monitor histogram; `trace=False`
    keeps high-frequency timers out of the chrome timeline
    (aggregate-only); `args` adds extra chrome-trace event args. Step,
    trace ids and args ride the annotation as keyword arguments too."""
    if not enabled():
        return TraceAnnotation(name)
    if step is None:
        step = current_step()
    return _Span(name, step, track, cat, timer, trace,
                 current_trace(), args)


def counter_sample(name: str, value: Optional[float] = None) -> None:
    """Embed one monitor counter sample into the chrome trace as a "C"
    event (value defaults to the counter's current reading)."""
    if not enabled():
        return
    if value is None:
        value = monitor.stat_get(name)
    profiler.add_counter_event(name, value)


# ---------------------------------------------------------------------------
# device-side names: HLO instruction -> scope path
# ---------------------------------------------------------------------------
# The profiler names a device operation by its HLO instruction
# (`%fusion.35 = ...`). The scope path the program gave it
# (`jit(step)/transpose(jvp(forward))/Linear/dot_general`) is in the
# trace file too, but on the event's METADATA (stat `tf_op`), which
# `jax.profiler.ProfileData` does not show (seen on the chip, PR 27). So
# the program keeps, for the steps it compiles, the table from
# instruction to path, read once from the compiled module's own text; a
# trace reader joins the two by name. TrainStep and
# core/program_accounting.py (the engine's steps, the Executor's) feed
# it at compile time. Bounded: the newest _DEVICE_PROGRAMS modules.

_DEVICE_PROGRAMS = 16
_device_ops: "OrderedDict[str, Dict[str, str]]" = OrderedDict()
_DEVICE_LOCK = threading.Lock()
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s=\s.*?\bop_name="([^"]*)"', re.M)


def _module_text(compiled) -> str:
    """The compiled module's text with names and metadata and little
    else: a Mosaic kernel's body alone is 100 KB of base64 a call, and
    BERT-base's step holds 88 of them."""
    try:
        from jax._src.lib import _jax
        opts = _jax.HloPrintOptions()
        opts.print_backend_config = False
        opts.print_large_constants = False
        opts.print_operand_shape = False
        opts.print_result_shape = False
        return compiled.runtime_executable().hlo_modules()[0].to_string(
            opts)
    except Exception:       # another jaxlib: the whole text, slower
        return compiled.as_text()


# A kernel call the COMPILER put in place of a jax operation names
# itself and loses the operation's path (`jax.lax.ragged_dot` on a TPU
# becomes `%ragged-dot-none = custom-call(...)` with
# `op_name="ragged-dot-none"`: the grouped expert products, a third of
# the expert family's step, read as unscoped on the chip, PR 38). Such
# a call lies where its operands were made.
_HLO_PATHLESS_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s=\scustom-call\(([^)]*)\).*?'
    r'\bop_name="([^"/]*)"', re.M)
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")


def _adopt_pathless_calls(text: str, table: Dict[str, str]) -> None:
    """Give each custom call whose `op_name` is no path the path of
    the first of its operands that has one, its own name last
    (`.../moe/moe_experts/ragged-dot-none`)."""
    for m in _HLO_PATHLESS_CALL.finditer(text):
        for operand in _HLO_OPERAND.findall(m.group(2)):
            path = table.get(operand, "")
            if "/" in path:
                table[m.group(1)] = "%s/%s" % (path.rsplit("/", 1)[0],
                                               m.group(3))
                break


def note_device_program(compiled) -> None:
    """Remember which scope path each instruction of one compiled
    program (a `jax.stages.Compiled`) lies under. An observation, never
    a dependency: any failure leaves the table as it was."""
    try:
        text = _module_text(compiled)
        name = _HLO_MODULE.match(text).group(1)
        table = {m.group(1): m.group(2)
                 for m in _HLO_OP_NAME.finditer(text)}
        _adopt_pathless_calls(text, table)
    except Exception:
        return
    with _DEVICE_LOCK:
        _device_ops.pop(name, None)
        _device_ops[name] = table
        while len(_device_ops) > _DEVICE_PROGRAMS:
            _device_ops.popitem(last=False)


def device_op_names() -> Dict[str, Dict[str, str]]:
    """{module name as the trace's `XLA Modules` line has it, less the
    id in brackets: {HLO instruction name: scope path}}."""
    with _DEVICE_LOCK:
        return dict(_device_ops)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

_FLIGHT_LOCK = threading.Lock()
_flight: deque = deque(maxlen=64)
_NOTE_TAG = "telemetry flight recorder"


def _resize_locked() -> None:
    cap = int(get_flag("FLAGS_telemetry_flight_steps", 64) or 64)
    global _flight
    if _flight.maxlen != cap:
        _flight = deque(_flight, maxlen=max(1, cap))


def flight_begin(step: int, **fields: Any) -> Dict[str, Any]:
    """Open (or update) the flight record for `step`. Records hold
    step id, t_begin_us, and whatever the caller annotates via
    flight_note (program key, dispatch/drain timestamps, sync count)."""
    with _FLIGHT_LOCK:
        _resize_locked()
        for rec in reversed(_flight):
            if rec.get("step") == step:
                rec.update(fields)
                return rec
        rec = {"step": step, "t_begin_us": now_us(), **fields}
        _flight.append(rec)
        return rec


def flight_note(step: Optional[int], key: str, value: Any = None,
                add: Optional[float] = None) -> None:
    """Annotate the record for `step` (searched newest-first; no-op if
    it already scrolled off). `add` increments a numeric field instead
    of assigning."""
    if step is None:
        return
    with _FLIGHT_LOCK:
        for rec in reversed(_flight):
            if rec.get("step") == step:
                if add is not None:
                    rec[key] = rec.get(key, 0) + add
                else:
                    rec[key] = value
                return


def flight_records() -> List[Dict[str, Any]]:
    with _FLIGHT_LOCK:
        return [dict(r) for r in _flight]


def flight_reset() -> None:
    with _FLIGHT_LOCK:
        _flight.clear()


def flight_dump() -> str:
    """Human-readable dump of the last N step records, newest last."""
    recs = flight_records()
    if not recs:
        return "%s: empty" % _NOTE_TAG
    lines = ["%s (last %d steps):" % (_NOTE_TAG, len(recs))]
    for r in recs:
        parts = ["step=%s" % r.get("step")]
        for k in sorted(r):
            if k in ("step",):
                continue
            v = r[k]
            if isinstance(v, float):
                parts.append("%s=%.1f" % (k, v))
            else:
                parts.append("%s=%s" % (k, v))
        lines.append("  " + " ".join(parts))
    return "\n".join(lines)


def attach_flight(exc: BaseException) -> None:
    """Append the flight dump to `exc` (PEP 678 notes) exactly once —
    the exception message path that turns 'NaN at some step' into a
    reconstructable timeline."""
    if not enabled():
        return
    notes = getattr(exc, "__notes__", None) or ()
    if any(_NOTE_TAG in n for n in notes):
        return
    note = flight_dump()
    try:
        exc.add_note(note)
    except AttributeError:
        # pre-3.11: no add_note, but __notes__ is just an attribute and
        # 3.11+ traceback formatting (and our tests) read it the same way
        try:
            if getattr(exc, "__notes__", None) is None:
                exc.__notes__ = []
            exc.__notes__.append(note)
        except Exception:
            pass
    except Exception:
        pass
