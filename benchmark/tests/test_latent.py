"""The latent family's driver, reference and two readers, rehearsed on the CPU at
toy sizes (`data/configs/toy_kimi.json`: 5 layers, 4 of 16 experts held, 4 heads
over one latent row of 16 + 8), with a spec built here: the recorded
`data/BENCHMARK.json` is left as it is."""
import json
import sys

import numpy as np
import pytest

from helpers import DATA, SPEC, rehearse

from benchmark import harness, trace_scopes

CELL, OLD = "toy_kimi_agent_c4", "toy_gpt2_chat_c4"
NEW_METRICS = (("latent_attn_device_ms", "ms"),
               ("latent_attn_roofline_pct", "%"), ("moe_peak_load_ratio",
                                                   "ratio"))
REAL = "kimi_k2_6"


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    s = json.load(open(SPEC))
    s["configs"].append({"name": "toy_kimi", "source": "toy", "file": "x",
                         "reduced": [], "why": "rehearsal"})
    s["workloads"].append({"name": CELL, "config": "toy_kimi",
                           "traffic": "agent_c4", "chips": 1,
                           "why": "rehearsal of the latent family"})
    for m in s["end_to_end"] + s["per_layer"]:
        if OLD in m.get("workloads", []):
            m["workloads"].append(CELL)
    for name, unit in NEW_METRICS:
        s["per_layer"].append({
            "name": name + ".serve", "unit": unit, "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_out_tokens_per_s", "workloads": [CELL]})
    p = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    p.write_text(json.dumps(s))
    return str(p)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_driver_serves_the_latent_family_and_is_correct(spec, trace):
    r = rehearse(CELL, seed=2 ** 31 + 91, trace=trace, spec=spec)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["served_logit_gap"]["value"] <= 1e-4
    if trace:
        # a rehearsal has no peak and no device plane: the two device readers
        # leave the line out, the counters' metrics are there
        assert {"engine_step_ms.serve", "slot_fill_pct.serve",
                "window_compiles.serve",
                "moe_peak_load_ratio.serve"} <= set(r["metrics"])
        assert not set(r["metrics"]) & {"latent_attn_device_ms.serve",
                                        "latent_attn_roofline_pct.serve"}
        assert r["metrics"]["window_compiles.serve"]["value"] == 0
    else:
        assert set(r["metrics"]) == {"serve_out_tokens_per_s", "tpot_p90_ms",
                                     "setup_s"}


def test_the_driver_builds_the_family_with_one_latent_pool(spec, monkeypatch):
    files = harness.Files(spec, [DATA])
    mod = files.load_module("drivers", "generation_pool_latent")
    seen = {}
    real = mod.Driver.window

    def window(self, seconds):
        out = real(self, seconds)
        e = self.engine
        seen.update(out["counters"], cfg=e.cfg, pools=list(e._pool_specs()),
                    shape=e.latent_pools.shape, chunk=e.prefill_chunk,
                    budget=e.token_budget)
        return out
    monkeypatch.setattr(mod.Driver, "window", window)
    rehearse(CELL, spec=spec)
    cfg = seen["cfg"]
    assert type(cfg).__name__ == "LatentDecoderConfig"
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held) == (16, 4, 4)
    assert cfg.max_seq_len == 64
    # ONE pool of five layers of latent rows, 16 + 8 in 128 lanes
    assert seen["pools"] == ["latent_pools"]
    assert seen["shape"] == (5, 64, 16, 128)
    # what the deployment fixes reaches the engine
    assert (seen["chunk"], seen["budget"]) == (8, 12)
    # summed over five layers that all see the whole context
    assert seen["attended_slots"] >= 5 * seen["tokens"] > 0
    # each lane's context once a step: never more than every slot's; the
    # step's roofline reads the rows as its `attended_tokens`
    assert 0 < seen["context_rows"] <= seen["attended_slots"]
    assert seen["attended_tokens"] == seen["context_rows"]
    assert 0 < seen["moe_experts_touched"] <= seen["steps"] * 4 * 4


def test_a_planted_fault_is_not_correct(spec, monkeypatch):
    from paddle_tpu.generation.engine import GenerationEngine
    real = GenerationEngine._retire

    def altered(self, lane, reason):
        res = real(self, lane, reason)
        res.tokens[3] = (res.tokens[3] + 1) % self.cfg.vocab_size
        return res
    monkeypatch.setattr(GenerationEngine, "_retire", altered)
    r = rehearse(CELL, spec=spec)
    assert r["correct"] is False
    wide = r["compared"]["served_logit_gap"]
    assert wide["value"] > 100 * wide["limit"]


@pytest.mark.parametrize("control", ["fp8_latent", "no_mscale"])
def test_the_controls_are_not_correct(spec, control):
    """Bfloat16 weights as the real cell's; the control's first choices lie
    outside the toy's limits by a hundred times and more."""
    R = harness.Files(spec, [DATA]).load_module("references", REAL)
    cfg = json.load(open(DATA + "/configs/toy_kimi.json"))
    w = R.make_weights(cfg, 3)
    ref = R.Reference(cfg, pad_to=64, new_tokens=32)
    rng = np.random.RandomState(3)
    gaps = []
    for _ in range(4):
        prompt = rng.randint(0, cfg["vocab_size"], 24)
        tail = rng.randint(0, cfg["vocab_size"], 32).tolist()
        gaps.append(ref.gaps(w, prompt, tail, control=control))
    got = R.compare(gaps)
    toy = harness.Files(spec, [DATA]).load_module("references", "toy_kimi")
    assert set(got) == set(R.LIMITS) == set(toy.LIMITS)
    assert got["served_logit_gap_mean"] > 100 * toy.LIMITS[
        "served_logit_gap_mean"]


def _ctx(cfg, **kw):
    ctx = {"config": cfg, "cell": {"chips": 1}, "kind": "serve", "notes": {},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_s": 10.0, "planes": None, "trace": None,
           "counters": {"steps": 100, "attended_slots": 500_000_000,
                        "context_rows": 270_000_000,
                        "attended_tokens": 270_000_000,
                        "moe_pairs": 9000, "moe_experts_touched": 4700,
                        "moe_peak_load": 1500, "experts_held": 12,
                        "sparse_layers": 4,
                        "finished": [(4096, 2048)] * 12}}
    ctx.update(kw)
    return ctx


def test_the_counts_against_hand_counts():
    from benchmark.references import kimi_k2_6 as R
    cfg = json.load(open(harness.HERE + "/configs/kimi_k2_6.json"))
    z = R.sizes(cfg)
    attn = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    expert = 3 * 7168 * 2048
    assert R._attn_params(z) == attn == 101_122_048
    assert R._expert_params(z) == expert == 44_040_192
    # a row of 512 + 64 bfloat16 values a position a layer
    assert R.latent_bytes(cfg, 1) == 1152
    assert R.latent_flops(cfg, 1) == 2 * 64 * (512 + 64 + 512) == 139_264
    # 8 of 384 choices land on 12 held experts: a quarter of a pair expected
    # a token a layer, beside the shared expert
    n = 4096 + 2048 - 1
    per_token = (5 * attn + 3 * 7168 * 18432
                 + 4 * (7168 * 384 + (1 + 0.25) * expert))
    want = (2 * per_token * n + 2 * 64 * (192 + 128) * 5 * n * (n + 1) // 2
            + 2 * 7168 * 20480 * 2048)
    assert R.request_flops(cfg, 4096, 2048) == pytest.approx(want, rel=1e-12)
    assert R.expert_bytes(cfg, 48) == 48 * expert * 2
    # a step: every weight held but the embedding once (the head 7168 x
    # 20480 among them), 6.70 GB, and 1,152 B a row read
    held = 3_496_763_904 - 7168 * 20480
    assert R.step_bytes(cfg, 1, 0) == 2 * held
    assert R.step_bytes(cfg, 0, 1) == 1152
    assert R.step_bytes(cfg, 3, 7) == 3 * R.step_bytes(cfg, 1, 0) + 7 * 1152


def _traced(monkeypatch, scope):
    """A recorded trace whose step's program ran twice, with the scope
    `sampler` standing in for `scope`."""
    d = json.load(open(DATA + "/trace_scoped.json"))
    mod = next(iter(d["op_names_serve"]))
    for l in d["planes"][0]["lines"]:
        if l["name"] == "XLA Modules":
            for e in l["events"]:
                e[0] = e[0].replace("jit_step", mod)
    names = {m: {i: p.replace("/sampler/", "/%s/" % scope)
                 for i, p in t.items()}
             for m, t in d["op_names_serve"].items()}
    assert any("/%s/" % scope in p for t in names.values() for p in t.values())
    monkeypatch.setattr(trace_scopes, "program_names", lambda: names)
    return d["planes"], mod


def test_the_readers_read_the_configurations_own_counts(spec, monkeypatch):
    files = harness.Files(spec, [DATA])
    cfg = json.load(open(harness.HERE + "/configs/kimi_k2_6.json"))
    R = files.load_module("references", REAL)
    planes, mod = _traced(monkeypatch, "latent_attention")
    ctx = _ctx(cfg, planes=planes)
    ms = files.load_module("metrics", "latent_attn_device_ms").read(ctx)
    red = trace_scopes.device(ctx)
    runs = trace_scopes.runs(red, mod)
    assert ms == pytest.approx(
        1e3 * trace_scopes.under(red, "latent_attention") / runs)
    assert ms > 0
    got = files.load_module("metrics", "latent_attn_roofline_pct").read(ctx)
    note = ctx["notes"]["latent_attn_roofline"]
    attended, rows = 500_000_000 / 100, 270_000_000 / 100
    assert (note["attended_a_step"], note["rows_a_step"]) == (attended, rows)
    # the bytes of each lane's rows once, the products of every slot's: here
    # the rows' stream is the longer
    need = R.latent_bytes(cfg, rows) / 819e9
    assert R.latent_flops(cfg, attended) / 197e12 < need
    assert note["need_ms_a_step"] == pytest.approx(1e3 * need)
    assert got == pytest.approx(100 * need / (ms / 1e3))
    # the whole step's share: the weights once and each lane's rows once,
    # over the mixed program's device time a run
    step = files.load_module("metrics", "step_hbm_roofline_pct").read(ctx)
    note = ctx["notes"]["step_hbm_roofline"]
    assert note["bytes_a_step"] == R.step_bytes(cfg, 100, 270_000_000) / 100
    assert note["bytes_a_step"] < R.step_bytes(cfg, 1, attended)
    assert step == pytest.approx(100 * note["bytes_a_step"]
                                 / (note["device_s_a_step"] * 819e9))


@pytest.mark.parametrize("name,lacks", [
    ("latent_attn_device_ms", "trace"), ("latent_attn_device_ms", "scope"),
    ("latent_attn_roofline_pct", "trace"),
    ("latent_attn_roofline_pct", "scope"),
    ("latent_attn_roofline_pct", "counters"),
    ("latent_attn_roofline_pct", "rows"),
    ("latent_attn_roofline_pct", "counts"),
    ("latent_attn_roofline_pct", "peak")])
def test_a_reader_that_finds_nothing_returns_none(spec, name, lacks,
                                                  monkeypatch):
    """As on an older commit (no counter, no scope: a trace that names other
    scopes gives None, and does not raise), on a rehearsal (no peak, no device
    plane) and for a reference module with no counts of its own."""
    files = harness.Files(spec, [DATA])
    files.load_module("references", REAL)
    files.load_module("references", "toy_gpt2")
    read = files.load_module("metrics", name).read
    ctx = _ctx({"reference": "toy_gpt2" if lacks == "counts" else REAL})
    if lacks == "counters":
        ctx["counters"] = {"steps": 100}
    if lacks == "rows":
        del ctx["counters"]["context_rows"]
    if lacks == "peak":
        ctx["peak"] = None
    if lacks == "scope":
        d = json.load(open(DATA + "/trace_scoped.json"))
        monkeypatch.setattr(trace_scopes, "program_names",
                            lambda: d["op_names_serve"])
        ctx["planes"] = d["planes"]
    assert read(ctx) is None


def test_an_older_program_fails_at_once_on_the_new_cell(spec, monkeypatch):
    """A program without the latent family (an older commit with these
    benchmark files laid over it): the cell's first import fails, before a
    request is drawn or a weight made."""
    monkeypatch.setitem(sys.modules, "paddle_tpu.generation.mla_moe", None)
    files = harness.Files(spec, [DATA])
    drv = files.load_module("drivers", "generation_pool_latent")
    made = []
    monkeypatch.setattr(drv.Driver, "_draw_requests",
                        lambda self: made.append(1))
    with pytest.raises(ImportError):
        rehearse(CELL, spec=spec)
    assert not made
