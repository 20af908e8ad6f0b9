"""The readers of the engine's device-side time (PR 40) on a small synthetic
trace, built here.

Device 0 (ns): operations [100,300) and [520,900) inside `bench/traced`
[0,1000) on the main thread: idle [0,100), [300,520), [900,1000), 420 of 1000.

The pool's serving thread, two engine steps:
- `pt/engine/step` [0,480): `admit` [0,20), `plan` [20,120) holding a
  copy-on-write's `DevicePutWithSharding` [60,70), `dispatch` [120,200)
  holding the call's `PjitFunction` [120,200) and in it two `DevicePut`
  [130,155) and [155,180), `fetch` [200,400) holding `pt/device/wait`
  [210,380), `advance` [400,430), `emit` [430,470); the step's own [470,480);
- `pt/pool/wait` [480,510); a `DevicePut` under no span [510,515); nothing
  open [515,520);
- `pt/engine/step` [520,1000): `dispatch` [520,600) holding `PjitFunction`
  [520,600) and its `DevicePut` [530,560), `fetch` [600,950) holding `wait`
  [600,900), `emit` [950,990).

A second thread holds `pt/pool/deliver` [0,1000), which the idle split leaves
out. The idle split: admit 20, plan 40 + 30, the transfer 10, wait 80, fetch
20 + 50, advance 30, emit 40 + 40, the step's own 10 + 10, pool wait 30, no
span 5 + 5.
"""
import os

import pytest

from benchmark import harness

NEW = ("engine_upload_ms.serve", "engine_launch_ms.serve",
       "engine_fetch_wait_ms.serve", "idle_unspanned_pct.serve")
SERVE_CELLS = ("gpt2_124m_chat_c32", "ouro_2_6b_reason_c16",
               "k_exaone_236b_reason_c128")
STEP_SPLIT = {"pt/engine/admit": 20, "pt/engine/plan": 70,
              "DevicePut": 10, "pt/device/wait": 80,
              "pt/engine/fetch": 70, "pt/engine/advance": 30,
              "pt/engine/emit": 80, "pt/engine/step": 20,
              "pt/pool/wait": 30, "(no span)": 10}
TRANSFERS = ("DevicePut", "DevicePutWithSharding")
# what the parent of PR 40 does not open
NEW_SPANS = ("pt/device/", "pt/engine/advance")


def _ev(name, start, end):
    return [name, float(start), float(end - start), {}]


def _planes():
    engine = [_ev("pt/engine/step", 0, 480), _ev("pt/engine/admit", 0, 20),
              _ev("pt/engine/plan", 20, 120),
              _ev("DevicePutWithSharding", 60, 70),
              _ev("pt/engine/dispatch", 120, 200),
              _ev("PjitFunction(jit(generation_mixed_0))", 120, 200),
              _ev("DevicePut", 130, 155), _ev("DevicePut", 155, 180),
              _ev("pt/engine/fetch", 200, 400),
              _ev("pt/device/wait", 210, 380),
              _ev("pt/engine/advance", 400, 430),
              _ev("pt/engine/emit", 430, 470), _ev("pt/pool/wait", 480, 510),
              _ev("DevicePut", 510, 515),
              _ev("pt/engine/step", 520, 1000),
              _ev("pt/engine/dispatch", 520, 600),
              _ev("PjitFunction(jit(generation_mixed_0))", 520, 600),
              _ev("DevicePut", 530, 560),
              _ev("pt/engine/fetch", 600, 950),
              _ev("pt/device/wait", 600, 900),
              _ev("pt/engine/emit", 950, 990)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _ev("jit_generation_mixed(1)", 100, 300),
                _ev("jit_generation_mixed(1)", 520, 900)]},
            {"name": "XLA Ops", "events": [
                _ev("%fusion.1 = f32[8] fusion()", 100, 300),
                _ev("%fusion.1 = f32[8] fusion()", 520, 900)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [_ev("bench/traced", 0, 1000)]},
            {"name": "serve", "events": engine},
            {"name": "deliver", "events": [
                _ev("pt/pool/deliver", 0, 1000)]}]}]


def _ctx(planes=None):
    return {"planes": _planes() if planes is None else planes, "notes": {},
            "kind": "serve"}


def _read(name, ctx):
    return harness.Files().load_module(
        "metrics", name.partition(".")[0]).read(ctx)


def _without(planes, names):
    for p in planes:
        for l in p["lines"]:
            l["events"] = [e for e in l["events"]
                           if not e[0].startswith(names)]
    return planes


@pytest.mark.parametrize("name,want", [
    ("engine_upload_ms.serve", (10 + 50 + 30) / 2 * 1e-6),
    ("engine_launch_ms.serve", ((80 - 50) + (80 - 30)) / 2 * 1e-6),
    ("engine_fetch_wait_ms.serve", (170 + 300) / 2 * 1e-6),
    ("idle_unspanned_pct.serve", 100.0 * (10 + 20) / 420),
])
def test_each_reader_on_the_synthetic_trace(name, want):
    assert abs(_read(name, _ctx()) - want) < 1e-12


def test_the_idle_split_adds_up_to_the_devices_idle_time():
    from benchmark import trace_reduce
    ctx = _ctx()
    _read("idle_unspanned_pct.serve", ctx)
    note = ctx["notes"]["idle_by_engine_span"]
    red = trace_reduce.reduce(ctx["planes"])
    assert abs(note["idle_s"] - (red["window_s"] - red["busy_s"])) < 1e-15
    assert note["steps"] == 2.0
    got = {n: round(ms * 1e6 * note["steps"])
           for n, ms in note["ms_a_step"].items()}
    assert got == STEP_SPLIT
    assert sum(got.values()) == 420


def test_idle_time_under_no_span_is_unspanned():
    """Take `pt/pool/wait` away: its 30 ns of idle time fall under no span,
    beside the 5 of the transfer that no span holds and the 5 of nothing."""
    planes = _planes()
    serve = planes[1]["lines"][1]
    serve["events"] = [e for e in serve["events"] if e[0] != "pt/pool/wait"]
    ctx = _ctx(planes)
    assert abs(_read("idle_unspanned_pct.serve", ctx)
               - 100.0 * (40 + 20) / 420) < 1e-12
    ms = ctx["notes"]["idle_by_engine_span"]["ms_a_step"]
    assert round(ms["(no span)"] * 1e6 * 2) == 40


def test_a_span_on_another_thread_is_ignored():
    """`pt/pool/deliver` spans the whole slice on its own thread, and a
    further thread with one step of its own: the serving thread still
    holds the most steps, and nothing of the others enters the split."""
    planes = _planes()
    planes[1]["lines"].append({"name": "other", "events": [
        _ev("pt/engine/step", 0, 100)]})
    ctx = _ctx(planes)
    _read("idle_unspanned_pct.serve", ctx)
    ms = ctx["notes"]["idle_by_engine_span"]["ms_a_step"]
    assert "pt/pool/deliver" not in ms
    assert sum(ms.values()) * 1e6 * ctx["notes"]["idle_by_engine_span"][
        "steps"] == pytest.approx(420)


@pytest.mark.parametrize("name,without", [
    ("engine_upload_ms.serve", TRANSFERS),
    ("engine_launch_ms.serve", TRANSFERS),
    ("engine_fetch_wait_ms.serve", ("pt/device/wait",)),
    ("idle_unspanned_pct.serve", ("pt/engine/step",)),
])
def test_each_reader_is_none_without_what_it_reads(name, without):
    """No transfer event, no wait span, no engine step: nothing to read. No
    trace at all, and for the idle split no device plane (the CPU
    rehearsal): nothing either. No reader raises."""
    assert _read(name, _ctx(_without(_planes(), without))) is None
    assert _read(name, {"planes": None, "notes": {}, "kind": "serve"}) \
        is None
    if name.startswith("idle"):
        host_only = [p for p in _planes()
                     if not p["name"].startswith("/device:")]
        assert _read(name, _ctx(host_only)) is None


@pytest.mark.parametrize("name,want", [
    ("engine_upload_ms.serve", (10 + 50 + 30) / 2 * 1e-6),
    ("engine_launch_ms.serve", ((80 - 50) + (80 - 30)) / 2 * 1e-6),
    ("engine_fetch_wait_ms.serve", None),
    ("idle_unspanned_pct.serve", 100.0 * (10 + 20 + 30) / 420),
])
def test_the_parents_trace(name, want):
    """The parent of PR 40 opens no `pt/device/wait` and no
    `pt/engine/advance`, but its runtime records the same transfers: the
    upload and the launch read as they do here, the advance's 30 ns of idle
    time are the step's own."""
    got = _read(name, _ctx(_without(_planes(), NEW_SPANS)))
    assert got == want if want is None else abs(got - want) < 1e-12


def test_the_new_entries_have_the_serve_cells():
    """Held by NAME: later PRs append entries of their own and may add their
    cells to these."""
    spec = harness.Files().spec
    by_name = {m["name"]: m for m in spec["per_layer"]}
    serve = next(m for m in spec["end_to_end"]
                 if m["name"] == "serve_out_tokens_per_s")["workloads"]
    for name in NEW:
        m = by_name[name]
        assert set(SERVE_CELLS) <= set(m["workloads"]) <= set(serve)
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_span", "entry points", "serve_out_tokens_per_s",
            "lower")


def test_cpu_rehearsal_reads_the_host_spans():
    """A whole traced run of the toy serve cell with the four entries: the
    three host readers find the spans and XLA:CPU's own `DevicePut` in the
    profiler's trace, the idle split finds no device plane and is left
    out."""
    from benchmark.tests.helpers import DATA, SERVE, rehearse
    r = rehearse(SERVE, trace=1, seconds=2.0,
                 spec=os.path.join(DATA, "BENCHMARK_device_spans.json"))
    assert r["correct"] is True
    for n in ("engine_upload_ms.serve", "engine_launch_ms.serve",
              "engine_fetch_wait_ms.serve"):
        assert r["metrics"][n]["value"] > 0
    assert "idle_unspanned_pct.serve" not in r["metrics"]
    spans = dict(r["notes"]["host_by_span"]["self_s"])
    for n in ("pt/device/wait", "pt/engine/advance"):
        assert spans[n] >= 0
