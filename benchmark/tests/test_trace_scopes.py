"""Device time by the program's scopes and host time by its spans, on a small
recorded structure (`data/trace_scoped.json`).

Device (ns), two executions of `jit_step` at 0 and 1000 and one of
`jit__threefry_split` at 2050. Each execution of the step: fusion.1
[10,210) forward/Linear; while.2 [220,520) forward/mlp holding fusion.3
[230,330) and [340,440); flash_attention_fwd.4 [530,580); fusion.5 [600,750)
backward/Linear; flash_attention_bwd_dq.6 [760,800) backward; fusion.7
[810,910) optimizer (in the second execution fusion.77, its path in the
event's own `tf_op`); copy.8 [920,980) with no path. The third module's
fusion.1 [2055,2065) shares its NAME with the step's and has no table: 900 +
900 + 10 = 1810 busy of the 2100 traced. The `Async XLA Ops` line is not
counted, as in trace_reduce.

Host: on the main thread two `pt/trainstep/call` of 50 with `stage` 10 and
`dispatch` 30 inside `bench/step`; on a second thread two rounds of the
pool's loop: wait 20, admit 10, `pt/engine/step` 400 (admit 10, plan 80,
dispatch 50, fetch 180, emit 40: 360 covered), deliver 15.
"""
import copy
import json
import os

import pytest

from benchmark import harness, trace_reduce, trace_scopes

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9


def _data():
    return json.load(open(os.path.join(HERE, "data", "trace_scoped.json")))


def _ctx(kind="train", names="op_names", planes=None):
    d = _data()
    planes = planes if planes is not None else d["planes"]
    table = d[names] if names else {}
    if kind == "serve":
        mod = next(iter(d["op_names_serve"]))
        for l in planes[0]["lines"]:
            if l["name"] == "XLA Modules":
                for e in l["events"]:
                    e[0] = e[0].replace("jit_step", mod)
    return {"planes": planes, "notes": {}, "kind": kind}, table


@pytest.fixture
def program(monkeypatch):
    """Stands for `paddle_tpu.telemetry.device_op_names()`."""
    def use(table):
        monkeypatch.setattr(trace_scopes, "program_names", lambda: table)
    return use


def _read(name, ctx):
    base = name.partition(".")[0]
    return harness.Files().load_module("metrics", base).read(ctx)


@pytest.mark.parametrize("path,scopes,backward", [
    ("jit(step)/jvp(forward)/BertModel/Linear/dot_general",
     ["forward", "BertModel", "Linear"], False),
    ("jit(step)/transpose(jvp(forward))/BertModel/LayerNorm/layer_norm/"
     "layer_norm_bwd/pallas_call:",
     ["forward", "BertModel", "LayerNorm", "layer_norm", "layer_norm_bwd"],
     True),
    ("jit(step)/jvp(forward)/Dropout/jit(_bernoulli)/jit(_uniform)/while/"
     "body/closed_call/TrainStep._make_loss_of.<locals>.f/shift_right",
     ["forward", "Dropout"], False),
    ("jit(generation_mixed)/sampler/jit(sample_tokens)/sampler/vmap(sort)/"
     "sort", ["sampler", "sampler", "sort"], False),
    ("jit(step)/optimizer/add", ["optimizer"], False),
    ("jit(step)/add", [], False),
], ids=["forward", "backward_kernel", "jax_words", "vmap", "optimizer",
        "none"])
def test_the_programs_components_of_a_path(path, scopes, backward):
    assert trace_scopes.scopes_of(path) == (scopes, backward)


def test_instruction_and_module_names():
    assert trace_scopes.instruction_of(
        "%fusion.35 = f32[2048]{0} fusion(f32[2048]{0} %sin.3), kind=kLoop") \
        == "fusion.35"
    assert trace_scopes.module_of("jit_step(11786659159095024637)") == \
        "jit_step"


def test_by_scope_times_add_up_to_the_busy_time():
    d = _data()
    red = trace_scopes.device_by_scope(d["planes"], d["op_names"])
    want = {"Linear": 400, "mlp": 600, "flash_attention_fwd": 100,
            "Linear~bwd": 300, "flash_attention_bwd_dq~bwd": 80,
            "optimizer": 200, "unscoped": 130}
    assert {k: round(v / NS) for k, v in red["by_scope"].items()} == want
    assert abs(red["busy_s"] - 1810 * NS) < 1e-15
    assert abs(red["busy_s"] - trace_reduce.reduce(d["planes"])["busy_s"]) \
        < 1e-15
    assert red["named"]
    # what `unscoped` is made of, by module and instruction
    left = {k.split(" = ")[0]: round(v / NS)
            for k, v in red["unscoped"].items()}
    assert left == {"jit_step %copy.8": 120, "jit__threefry_split %fusion.1":
                    10}
    assert abs(trace_scopes.runs(red, "jit_step") - 2.0) < 1e-12
    assert abs(trace_scopes.runs(red, "jit__threefry") - 1.0) < 1e-12


def test_a_loop_does_not_count_its_body_twice_and_passes_are_told_apart():
    d = _data()
    red = trace_scopes.device_by_scope(d["planes"], d["op_names"])
    under = trace_scopes.under
    assert round(under(red, "mlp") / NS) == 600          # 2 x (100 + 200)
    assert round(under(red, "forward", backward=False) / NS) == 1100
    assert round(under(red, "forward", backward=True) / NS) == 380
    assert round(under(red, "flash_attention") / NS) == 180
    assert round(under(red, "optimizer") / NS) == 200    # table and tf_op


def test_the_same_instruction_name_in_another_module_is_not_joined():
    d = _data()
    red = trace_scopes.device_by_scope(d["planes"], d["op_names"])
    # jit__threefry_split's fusion.1 has no table: unscoped, not Linear
    assert round(red["by_scope"]["Linear"] / NS) == 400
    assert round(red["by_scope"]["unscoped"] / NS) == 2 * 60 + 10


def test_events_are_clipped_to_the_traced_span():
    d = _data()
    d["planes"][1]["lines"][0]["events"][0] = ["bench/traced", 500, 1600, {}]
    red = trace_scopes.device_by_scope(d["planes"], d["op_names"])
    # of the first execution [500,1000) is left: 20 of the loop, 50 + 150 +
    # 40 + 100 + 60
    assert round(red["busy_s"] / NS) == 420 + 900 + 10
    assert abs(trace_scopes.runs(red, "jit_step") - 1.5) < 1e-12
    assert abs(red["busy_s"] - trace_reduce.reduce(d["planes"])["busy_s"]) \
        < 1e-15


def test_host_self_time_by_span_on_two_threads():
    red = trace_scopes.host_by_span(_data()["planes"])
    self_ns = {k: round(v / NS) for k, v in red["self_s"].items()}
    assert self_ns["pt/engine/step"] == 2 * 40
    assert self_ns["pt/engine/fetch"] == 2 * 180
    assert self_ns["pt/pool/wait"] == 2 * 20
    assert self_ns["pt/trainstep/call"] == 2 * 10
    assert self_ns["pt/trainstep/dispatch"] == 2 * 30
    assert red["count"]["pt/engine/step"] == 2.0
    assert all(n.startswith("pt/") for n in red["self_s"])
    # the children cover 90 % of each engine step here
    assert 1 - self_ns["pt/engine/step"] / round(
        red["total_s"]["pt/engine/step"] / NS) == 0.9


TRAIN = {"forward_device_ms.train": 1100 / 2, "backward_device_ms.train":
         380 / 2, "optimizer_device_ms.train": 200 / 2,
         "flash_attn_device_ms.train": 180 / 2,
         "unscoped_device_pct.train": None, "trainstep_call_ms.train": 50}
SERVE = {"sampler_device_ms.serve": 80 / 2, "paged_attn_device_ms.serve":
         (600 + 100 + 300) / 2, "unscoped_device_pct.serve": None,
         "engine_host_ms.serve": 400 - 50 - 180 + 20 + 10 + 15}


@pytest.mark.parametrize("name", sorted(TRAIN) + sorted(SERVE))
def test_each_reader_on_the_recorded_trace(name, program):
    kind = name.partition(".")[2]
    ctx, table = _ctx(kind, "op_names" if kind == "train"
                      else "op_names_serve")
    program(table)
    got = _read(name, ctx)
    want = {**TRAIN, **SERVE}[name]
    if want is None:        # the share of the busy time under no scope
        assert abs(got - 100.0 * 130 / 1810) < 1e-9
    else:
        assert abs(got - want * 1e-6) < 1e-12      # ns -> ms
    # each run's result line carries the whole breakdown, not one number
    notes = ctx["notes"]
    if "device" in name:
        assert dict(notes["device_by_scope"]["seconds"])["unscoped"] > 0
        # the two executions' copy.8 read as one line, the other module's
        # fusion.1 as another
        rows = notes["device_by_scope"]["unscoped_ops"]
        assert [(r[0].split(" = ")[0].split(" ")[1], round(r[1] / NS), r[2])
                for r in rows] == [("%copy", 120, 1), ("%fusion", 10, 1)]
        assert abs(notes["device_by_scope"]["busy_s"] - 1810 * NS) < 1e-15
    else:
        assert dict(notes["host_by_span"]["self_s"])


@pytest.mark.parametrize("name", sorted(TRAIN) + sorted(SERVE))
def test_no_device_plane_or_no_names_reads_none(name, program):
    """The CPU rehearsal (no device plane) and the parent of PR 27 (a device
    plane, but a program that names nothing and opens no span): no reader
    raises, the line leaves the metric out."""
    kind = name.partition(".")[2]
    host_only, _ = _ctx(kind, None)
    host_only["planes"] = [p for p in host_only["planes"]
                           if not p["name"].startswith("/device:")]
    program({})
    if "device" in name:
        assert _read(name, host_only) is None
    parent, _ = _ctx(kind, None)
    for p in parent["planes"]:
        for l in p["lines"]:
            l["events"] = [e for e in l["events"]
                           if not e[0].startswith("pt/")
                           and "tf_op" not in e[3]]
    assert _read(name, parent) is None
    assert _read(name, {"planes": None, "notes": {}, "kind": kind}) is None


@pytest.mark.parametrize("name,lost", [
    ("sampler_device_ms.serve", "sampler"),
    ("paged_attn_device_ms.serve", "kv_write"),
    ("optimizer_device_ms.train", "optimizer"),
    ("flash_attn_device_ms.train", "flash_attention"),
    ("backward_device_ms.train", "forward"),
])
def test_a_name_lost_in_a_refactoring_fails_loudly(name, lost, program):
    kind = name.partition(".")[2]
    ctx, table = _ctx(kind, "op_names" if kind == "train"
                      else "op_names_serve")
    table = copy.deepcopy(table)
    for paths in table.values():
        for instr, path in paths.items():
            if name.startswith("backward"):
                path = path.replace("transpose(jvp(forward))", "jvp(forward)")
            paths[instr] = path.replace(lost + "/", "renamed/") \
                if not name.startswith("backward") else path
    for l in ctx["planes"][0]["lines"]:      # and the path in the stats
        for e in l["events"]:
            e[3].pop("tf_op", None)
    program(table)
    with pytest.raises(harness.BenchError, match=lost):
        _read(name, ctx)


def test_engine_host_reader_fails_where_the_step_lost_a_child(program):
    ctx, _ = _ctx("serve", None)
    for l in ctx["planes"][1]["lines"]:
        l["events"] = [e for e in l["events"] if e[0] != "pt/engine/fetch"]
    program({})
    with pytest.raises(harness.BenchError, match="pt/engine/fetch"):
        _read("engine_host_ms.serve", ctx)


def test_the_new_entries_are_the_last_of_per_layer_and_have_their_cells():
    """PR 27's entries, held by NAME and by their cells: later PRs append
    entries of their own and add their cells to these."""
    spec = harness.Files().spec
    by_name = {m["name"]: m for m in spec["per_layer"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {"train": "bert_base_mlm_b32_s512", "serve": "gpt2_124m_chat_c32"}
    for name in sorted(TRAIN) + sorted(SERVE):
        m = by_name[name]
        assert cells[name.partition(".")[2]] in m["workloads"]
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert m["unit"] in ("ms", "%") and m["better"] == "lower"


@pytest.mark.parametrize("cell,reports,children", [
    ("toy_bert_b4_s32", "trainstep_call_ms.train",
     ("pt/trainstep/call", "pt/trainstep/stage", "pt/trainstep/dispatch")),
    ("toy_gpt2_chat_c4", "engine_host_ms.serve",
     ("pt/engine/step", "pt/engine/plan", "pt/engine/dispatch",
      "pt/engine/fetch", "pt/engine/emit", "pt/pool/wait", "pt/pool/admit",
      "pt/pool/deliver")),
])
def test_cpu_rehearsal_with_the_new_entries(cell, reports, children):
    """The whole traced run with the new entries in the list (a copy of the
    toy BENCHMARK.json that holds them): the host readers find the program's
    spans in the profiler's trace, the device readers find no device plane on
    XLA:CPU and leave their metrics out, nothing raises."""
    from benchmark.tests.helpers import DATA, rehearse
    r = rehearse(cell, trace=1, seconds=2.0,
                 spec=os.path.join(DATA, "BENCHMARK_scoped.json"))
    assert r["correct"] is True
    assert r["metrics"][reports]["value"] > 0
    assert not any("device" in n for n in r["metrics"])
    spans = dict(r["notes"]["host_by_span"]["self_s"])
    for n in children:
        assert spans[n] >= 0
    assert "device_by_scope" not in r["notes"]
