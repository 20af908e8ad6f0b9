"""XLA program cost/memory accounting (docs/observability.md).

Every compiled program the runtime serves — Executor steps, Predictor
bucket executables, the generation engine's prefill/decode steps — is
captured HERE at compile time: ``jitted.lower(*args).compile()`` yields
the XLA executable plus its own accounting
(``compiled.cost_analysis()`` — flops, transcendentals, bytes accessed
— and ``compiled.memory_analysis()`` — argument/output/temp/
generated-code bytes). The record lands in a bounded process-global
registry and as ``GAUGE_program_*`` monitor instruments, so
``monitor.snapshot()``, ``/metrics``, and ``/programz``
(introspect.py) all see what every program on this process actually
costs — the numbers a TPU deployment plans capacity around (HBM
footprint per executable, achieved FLOP/s), not the analytic
hand-counts bench.py used to carry alone.

The capture is free in steady state: ``lower()`` is the trace the
first call would have paid anyway, ``compile()`` is the one XLA
compile, and the returned :class:`AccountedProgram` *is* the compiled
executable — the jitted fallback only runs (and recompiles, counted
``STAT_program_account_fallback``) if a later call's inputs don't
match the compiled signature, which the runtime's shape-pinned cache
keys make rare. Any failure inside the capture (cost analysis missing
on a backend, unlowerable args) degrades to the plain jitted callable:
accounting is an observation, never a dependency.

Process-wide aggregates:
- ``GAUGE_programs_count`` — live accounting records;
- ``GAUGE_programs_hbm_bytes`` — the compiled HBM footprint: sum over
  programs of argument+output+temp+generated-code bytes (what the
  executables pin, not what the allocator happens to hold);
- ``GAUGE_programs_flops_compiled`` — sum of per-program flops;
- ``GAUGE_programs_achieved_flops_per_s`` — sum(flops × calls) /
  process wall-time: FLOPs *dispatched* per second, refreshed on
  capture and on every scrape (``refresh_throughput``).
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

_LOCK = threading.Lock()
_PROGRAMS: "OrderedDict[str, ProgramRecord]" = OrderedDict()
_EPOCH = time.time()

# registry bound: programs outliving 512 distinct compiles (shape
# churn) age out oldest-first — the gauges of evicted entries are
# retracted so totals stay honest
_MAX_RECORDS = 512

_tls = threading.local()


def _stat_add(name: str, value: float = 1.0) -> None:
    from ..monitor import stat_add
    stat_add(name, value)


def _gauge_set(name: str, value: float) -> None:
    from ..monitor import gauge_set
    gauge_set(name, value)


class ProgramRecord:
    """Accounting for one compiled program."""

    __slots__ = ("tag", "key", "meta", "flops", "transcendentals",
                 "bytes_accessed", "argument_bytes", "output_bytes",
                 "temp_bytes", "generated_code_bytes", "alias_bytes",
                 "compile_seconds", "created_s", "calls")

    def __init__(self, tag: str, key: str, meta: Optional[dict]):
        self.tag = tag
        self.key = key
        self.meta = dict(meta or {})
        self.flops = 0.0
        self.transcendentals = 0.0
        self.bytes_accessed = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.generated_code_bytes = 0
        self.alias_bytes = 0
        self.compile_seconds = 0.0
        self.created_s = time.time() - _EPOCH
        self.calls = 0

    @property
    def hbm_bytes(self) -> int:
        """What this executable pins: arguments + outputs + scratch +
        the program text itself (aliased/donated bytes excluded — they
        reuse argument buffers)."""
        return int(self.argument_bytes + self.output_bytes +
                   self.temp_bytes + self.generated_code_bytes -
                   self.alias_bytes)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "tag": self.tag,
            "key": self.key,
            "meta": self.meta,
            # quant mode the program was traced under (engine meta
            # carries qm=; fp32/legacy programs report "off") — the
            # /programz answer to "which checkpoint flavor compiled
            # this" without digging through meta
            "quant": str(self.meta.get("qm", "off")),
            # autotune policy label the program was traced under
            # (engine meta carries policy=; untuned / legacy programs
            # report "") — the /programz answer to "which tuned
            # geometry compiled this" (docs/autotune.md)
            "policy": str(self.meta.get("policy", "")),
            "flops": self.flops,
            "transcendentals": self.transcendentals,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "alias_bytes": self.alias_bytes,
            "hbm_bytes": self.hbm_bytes,
            "compile_seconds": round(self.compile_seconds, 4),
            "age_s": round(time.time() - _EPOCH - self.created_s, 1),
            "calls": self.calls,
        }


def _cost_analysis(compiled) -> Dict[str, float]:
    """Defensive pull of compiled.cost_analysis(): jax returns a dict
    on some versions, a per-partition list of dicts on others, and
    some backends omit keys entirely."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    return ca


def _memory_analysis(compiled):
    try:
        return compiled.memory_analysis()
    except Exception:
        return None


def _fill_record(rec: ProgramRecord, compiled) -> None:
    ca = _cost_analysis(compiled)

    def cost(key):
        # XLA answers -1 where it does not know (an executable that
        # jax's persistent cache loaded back): unknown counts nothing
        return max(0.0, float(ca.get(key, 0.0) or 0.0))
    rec.flops = cost("flops")
    rec.transcendentals = cost("transcendentals")
    rec.bytes_accessed = cost("bytes accessed")
    ma = _memory_analysis(compiled)
    if ma is not None:
        for attr, field in (("argument_size_in_bytes", "argument_bytes"),
                            ("output_size_in_bytes", "output_bytes"),
                            ("temp_size_in_bytes", "temp_bytes"),
                            ("generated_code_size_in_bytes",
                             "generated_code_bytes"),
                            ("alias_size_in_bytes", "alias_bytes")):
            try:
                setattr(rec, field, int(getattr(ma, attr, 0) or 0))
            except Exception:
                pass


def _publish_locked(rec: ProgramRecord) -> None:
    base = "GAUGE_program_%%s_%s" % rec.tag
    _gauge_set(base % "flops", rec.flops)
    _gauge_set(base % "bytes_accessed", rec.bytes_accessed)
    _gauge_set(base % "temp_bytes", float(rec.temp_bytes))
    _gauge_set(base % "hbm_bytes", float(rec.hbm_bytes))


def _retract_locked(rec: ProgramRecord) -> None:
    from ..monitor import _GAUGES, _LOCK as _MLOCK
    base = "GAUGE_program_%%s_%s" % rec.tag
    with _MLOCK:
        for k in ("flops", "bytes_accessed", "temp_bytes", "hbm_bytes"):
            _GAUGES.pop(base % k, None)


def _publish_totals_locked() -> None:
    _gauge_set("GAUGE_programs_count", float(len(_PROGRAMS)))
    _gauge_set("GAUGE_programs_hbm_bytes",
               float(sum(r.hbm_bytes for r in _PROGRAMS.values())))
    _gauge_set("GAUGE_programs_flops_compiled",
               float(sum(r.flops for r in _PROGRAMS.values())))


def refresh_throughput() -> float:
    """Recompute GAUGE_programs_achieved_flops_per_s: FLOPs dispatched
    (sum of flops × calls) per wall-second of process lifetime. Called
    at capture time and by every introspect scrape, so the gauge is
    fresh wherever it is read."""
    with _LOCK:
        dispatched = sum(r.flops * r.calls for r in _PROGRAMS.values())
    dt = max(time.time() - _EPOCH, 1e-9)
    rate = dispatched / dt
    _gauge_set("GAUGE_programs_achieved_flops_per_s", rate)
    return rate


def record(compiled, *, tag: str, key: str = "",
           meta: Optional[dict] = None,
           compile_seconds: float = 0.0) -> ProgramRecord:
    """Register accounting for `compiled` under `tag` (re-recording a
    tag overwrites — a recompile of the same program replaces its
    numbers). Publishes the per-program gauges and the process totals."""
    rec = ProgramRecord(tag, key, meta)
    rec.compile_seconds = compile_seconds
    _fill_record(rec, compiled)
    # the device trace's readers tell operations by the program's
    # scopes through this table (telemetry.py)
    from .. import telemetry
    telemetry.note_device_program(compiled)
    with _LOCK:
        old = _PROGRAMS.pop(tag, None)
        if old is not None:
            rec.calls = old.calls
        _PROGRAMS[tag] = rec
        while len(_PROGRAMS) > _MAX_RECORDS:
            _, evicted = _PROGRAMS.popitem(last=False)
            _retract_locked(evicted)
            _stat_add("STAT_program_account_evict")
        _publish_locked(rec)
        _publish_totals_locked()
    refresh_throughput()
    return rec


class AccountedProgram:
    """The compiled executable, callable in place of the jitted fn it
    was lowered from. Falls back to the jitted path permanently on the
    first call whose inputs the compiled signature rejects (counted
    STAT_program_account_fallback; costs one recompile, never wrong
    results). Calls are tallied for the achieved-FLOP/s gauge."""

    __slots__ = ("_compiled", "_fallback", "record")

    def __init__(self, compiled, fallback, rec: ProgramRecord):
        self._compiled = compiled
        self._fallback = fallback
        self.record = rec

    def __call__(self, *args, **kwargs):
        compiled = self._compiled
        if compiled is not None:
            try:
                out = compiled(*args, **kwargs)
                self.record.calls += 1
                return out
            except (TypeError, ValueError):
                # signature mismatch is raised before execution (no
                # buffer was donated) — safe to retry via jit
                self._compiled = None
                _stat_add("STAT_program_account_fallback")
        out = self._fallback(*args, **kwargs)
        self.record.calls += 1
        return out


def accounted(jitted, example_args, *, tag: str, key: str = "",
              meta: Optional[dict] = None):
    """AOT-compile `jitted` against `example_args` (concrete values or
    ShapeDtypeStructs), record its XLA accounting, and return an
    :class:`AccountedProgram` serving the compiled executable. On any
    failure returns `jitted` unchanged — the caller's behavior without
    accounting."""
    try:
        t0 = time.perf_counter()
        compiled = jitted.lower(*example_args).compile()
        dt = time.perf_counter() - t0
    except Exception:
        _stat_add("STAT_program_account_errors")
        return jitted
    try:
        rec = record(compiled, tag=tag, key=key, meta=meta,
                     compile_seconds=dt)
    except Exception:
        _stat_add("STAT_program_account_errors")
        return jitted
    return AccountedProgram(compiled, jitted, rec)


# ---------------------------------------------------------------------------
# ambient tag labels — lets a layer above the Executor (the Predictor's
# bucket runner) name the entries its executions compile
# ---------------------------------------------------------------------------

class _TagScope:
    __slots__ = ("tag",)

    def __init__(self, tag: str):
        self.tag = tag

    def __enter__(self):
        stack = getattr(_tls, "tags", None)
        if stack is None:
            stack = _tls.tags = []
        stack.append(self.tag)
        return self

    def __exit__(self, *exc):
        _tls.tags.pop()
        return False


def tag_scope(tag: str) -> _TagScope:
    """Thread-locally label programs compiled inside the scope."""
    return _TagScope(tag)


def current_tag() -> Optional[str]:
    stack = getattr(_tls, "tags", None)
    return stack[-1] if stack else None


def safe_tag(text: str) -> str:
    """Collapse arbitrary text into a monitor/Prometheus-safe tag."""
    return "".join(c if c.isalnum() or c == "_" else "_" for c in text)


def key_token(obj: Any) -> str:
    """Short stable-within-process token for an unhashable/clunky cache
    key (repr-hash; used to make executor tags unique per entry)."""
    return hashlib.sha1(repr(obj).encode()).hexdigest()[:10]


# ---------------------------------------------------------------------------
# registry views
# ---------------------------------------------------------------------------

def programs() -> List[Dict[str, Any]]:
    """Accounting records, oldest first (the /programz payload)."""
    with _LOCK:
        return [r.as_dict() for r in _PROGRAMS.values()]


def totals() -> Dict[str, float]:
    with _LOCK:
        return {
            "count": len(_PROGRAMS),
            "hbm_bytes": float(sum(r.hbm_bytes
                                   for r in _PROGRAMS.values())),
            "flops_compiled": float(sum(r.flops
                                        for r in _PROGRAMS.values())),
            "calls": float(sum(r.calls for r in _PROGRAMS.values())),
        }


def reset() -> None:
    """Clear the registry and retract its gauges (test isolation)."""
    with _LOCK:
        for rec in _PROGRAMS.values():
            _retract_locked(rec)
        _PROGRAMS.clear()
        _publish_totals_locked()
