"""The expert family's driver, reference and three readers, rehearsed on the CPU
at toy sizes (`data/configs/toy_exaone.json`: 5 layers of the published kinds,
4 of 16 experts held, 8 heads over 2 key-value heads, a window of 8), with a
spec built here: the recorded `data/BENCHMARK.json` is left as it is."""
import json
import sys

import numpy as np
import pytest

from helpers import DATA, SPEC, rehearse

from benchmark import harness, trace_scopes

CELL, OLD = "toy_exaone_reason_c4", "toy_gpt2_chat_c4"
NEW_METRICS = (("config_mfu_pct", "%"), ("moe_device_ms", "ms"),
               ("moe_experts_hbm_roofline_pct", "%"),
               ("moe_peak_load_ratio", "ratio"))
REAL = "k_exaone_236b"


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    s = json.load(open(SPEC))
    s["configs"].append({"name": "toy_exaone", "source": "toy", "file": "x",
                         "reduced": [], "why": "rehearsal"})
    s["workloads"].append({"name": CELL, "config": "toy_exaone",
                           "traffic": "reason_c4", "chips": 1,
                           "why": "rehearsal of the expert family"})
    for m in s["end_to_end"] + s["per_layer"]:
        if OLD in m.get("workloads", []):
            m["workloads"].append(CELL)
    for name, unit in NEW_METRICS:
        s["per_layer"].append({
            "name": name + ".serve", "unit": unit, "better": "higher",
            "source": "device_trace", "layer": "model step",
            "moves": "serve_out_tokens_per_s", "workloads": [CELL]})
    p = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    p.write_text(json.dumps(s))
    return str(p)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_driver_serves_the_expert_family_and_is_correct(spec, trace):
    r = rehearse(CELL, seed=2 ** 31 + 77, trace=trace, spec=spec)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["served_logit_gap"]["value"] <= 1e-4
    assert r["compared"]["served_logit_gap_mean"]["value"] <= 1e-5
    if trace:
        # what a rehearsal can read: the counters' metrics. Those that need a
        # peak or a device plane leave the line out here
        assert {"engine_step_ms.serve", "slot_fill_pct.serve",
                "window_compiles.serve",
                "moe_peak_load_ratio.serve"} <= set(r["metrics"])
        assert not set(r["metrics"]) & {
            "config_mfu_pct.serve", "moe_device_ms.serve",
            "moe_experts_hbm_roofline_pct.serve"}
        assert r["metrics"]["window_compiles.serve"]["value"] == 0
        assert r["metrics"]["moe_peak_load_ratio.serve"]["value"] >= 1.0
    else:
        assert set(r["metrics"]) == {"serve_out_tokens_per_s", "tpot_p90_ms",
                                     "setup_s"}


def test_the_driver_counts_the_routing_and_builds_the_family(spec,
                                                            monkeypatch):
    files = harness.Files(spec, [DATA])
    mod = files.load_module("drivers", "generation_pool_expert")
    seen = {}
    real = mod.Driver.window

    def window(self, seconds):
        out = real(self, seconds)
        seen.update(out["counters"], cfg=self.engine.cfg,
                    pools=self.engine.k_pools.shape)
        return out
    monkeypatch.setattr(mod.Driver, "window", window)
    rehearse(CELL, spec=spec)
    cfg = seen["cfg"]
    assert type(cfg).__name__ == "ExpertDecoderConfig"
    # the router keeps its width; experts 4..7 of 16 are held
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held) == (16, 4, 4)
    assert cfg.sliding_windows == (8, 8, 8, 0, 8) and cfg.max_seq_len == 64
    # five cache layers, rows of kv_heads x head_dim
    assert seen["pools"] == (5, 64, 16, 16)
    assert (seen["experts_held"], seen["sparse_layers"]) == (4, 4)
    steps = seen["steps"]
    # a layer's busiest expert takes at least the mean and at most all pairs
    assert seen["moe_pairs"] / 4 <= seen["moe_peak_load"] <= seen["moe_pairs"]
    assert 0 < seen["moe_experts_touched"] <= steps * 4 * 4
    assert seen["moe_pairs"] >= seen["moe_experts_touched"]
    # summed over five layers, four of them capped at the window
    assert seen["attended_tokens"] > seen["tokens"] > 0


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
    # ONE wrong token: the widest gap is what sees it
    wide = r["compared"]["served_logit_gap"]
    assert wide["value"] > 100 * wide["limit"]


@pytest.mark.parametrize("control", ["fp8", "int8"])
@pytest.mark.parametrize("seed", [3, 4])
def test_the_controls_are_not_correct(spec, seed, control):
    """Bfloat16 weights as the real cell's; the control's first choices lie
    outside the toy's limit by a hundred times and more."""
    R = harness.Files(spec, [DATA]).load_module("references", REAL)
    cfg = json.load(open(DATA + "/configs/toy_exaone.json"))
    w = R.make_weights(cfg, seed)
    ref = R.Reference(cfg, pad_to=64, new_tokens=32)
    rng = np.random.RandomState(seed)
    gaps = []
    for _ in range(4):
        prompt = rng.randint(0, cfg["vocab_size"], 24)
        tail = rng.randint(0, cfg["vocab_size"], 32).tolist()
        gaps.append(ref.gaps(w, prompt, tail, control=control))
    got = R.compare(gaps)
    toy = harness.Files(spec, [DATA]).load_module("references", "toy_exaone")
    assert set(got) == set(R.LIMITS) == set(toy.LIMITS)
    # the MEAN gap is what tells a precision: a hundred times the toy's limit
    assert got["served_logit_gap_mean"] > 100 * toy.LIMITS[
        "served_logit_gap_mean"]
    assert got["served_logit_gap"] >= got["served_logit_gap_mean"]


def _ctx(cfg, **kw):
    ctx = {"config": cfg, "cell": {"chips": 1}, "kind": "serve", "notes": {},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_s": 10.0, "planes": None, "trace": None,
           "counters": {"steps": 100, "moe_pairs": 51200,
                        "moe_experts_touched": 6300, "moe_peak_load": 5600,
                        "experts_held": 16, "sparse_layers": 4,
                        "finished": [(64, 512)] * 12}}
    ctx.update(kw)
    return ctx


def test_the_counts_against_hand_counts():
    from benchmark.references import k_exaone_236b as R
    cfg = json.load(open(harness.HERE + "/configs/k_exaone_236b.json"))
    z = R.sizes(cfg)
    attn = 6144 * (8192 + 2 * 1024) + 8192 * 6144
    expert = 3 * 6144 * 2048
    assert R._attn_params(z) == attn == 113_246_208
    assert R._expert_params(z) == expert == 37_748_736
    # an expert layer's share here: attention, router, the shared expert and
    # sixteen routed experts: 755.7 M; the whole share 7.42 GB
    assert attn + 6144 * 128 + 17 * expert == 755_761_152
    n = 64 + 512 - 1
    # 8 of 128 choices land on 16 held experts: ONE pair expected a token a
    # layer, beside the shared expert
    per_token = (5 * attn + 3 * 6144 * 18432
                 + 4 * (6144 * 128 + (1 + 1.0) * expert))
    full = n * (n + 1) // 2
    win = full - (n - 128) * (n - 127) // 2
    want = (2 * per_token * n + 4 * 8192 * (full + 4 * win)
            + 2 * 6144 * 19200 * 512)
    assert R.request_flops(cfg, 64, 512) == want
    # a window layer attends at most 128 positions a query
    assert win == sum(min(i + 1, 128) for i in range(n))
    assert R.request_flops(cfg, 16, 8) == (
        2 * per_token * 23 + 4 * 8192 * 5 * (23 * 24 // 2)
        + 2 * 6144 * 19200 * 8)
    assert R.expert_bytes(cfg, 64) == 64 * expert * 2 == 4_831_838_208


def test_the_readers_read_the_configurations_own_counts(spec, monkeypatch):
    files = harness.Files(spec, [DATA])
    cfg = json.load(open(harness.HERE + "/configs/k_exaone_236b.json"))
    R = files.load_module("references", REAL)
    ratio = files.load_module("metrics", "moe_peak_load_ratio").read
    assert ratio(_ctx(cfg)) == pytest.approx(5600 / (51200 / 16)) == 1.75
    even = _ctx(cfg)
    even["counters"].update(moe_pairs=1600, moe_peak_load=100)
    assert ratio(even) == 1.0
    # the device readers: a recorded trace whose step's program ran twice,
    # with the scope `sampler` standing in for `moe` and `moe_experts`
    d = json.load(open(DATA + "/trace_scoped.json"))
    mod = next(iter(d["op_names_serve"]))
    for l in d["planes"][0]["lines"]:
        if l["name"] == "XLA Modules":
            for e in l["events"]:
                e[0] = e[0].replace("jit_step", mod)
    names = {m: {i: p.replace("/sampler/", "/moe/moe_experts/")
                 for i, p in t.items()}
             for m, t in d["op_names_serve"].items()}
    assert any("/moe/moe_experts/" in p for t in names.values()
               for p in t.values())
    monkeypatch.setattr(trace_scopes, "program_names", lambda: names)
    ctx = _ctx(cfg, planes=d["planes"])
    ms = files.load_module("metrics", "moe_device_ms").read(ctx)
    red = trace_scopes.device(ctx)
    runs = trace_scopes.runs(red, mod)
    assert ms == pytest.approx(1e3 * trace_scopes.under(red, "moe") / runs)
    assert ms > 0
    roof = files.load_module("metrics", "moe_experts_hbm_roofline_pct").read
    got = roof(ctx)
    note = ctx["notes"]["moe_experts_hbm_roofline"]
    assert note["bytes_a_step"] == R.expert_bytes(cfg, 6300) / 100
    assert note["device_ms_a_step"] == pytest.approx(ms)
    assert got == pytest.approx(100 * note["bytes_a_step"]
                                / (ms / 1e3 * 819e9))


@pytest.mark.parametrize("name,lacks", [
    ("moe_device_ms", "trace"), ("moe_device_ms", "scope"),
    ("moe_experts_hbm_roofline_pct", "trace"),
    ("moe_experts_hbm_roofline_pct", "scope"),
    ("moe_experts_hbm_roofline_pct", "counters"),
    ("moe_experts_hbm_roofline_pct", "counts"),
    ("moe_experts_hbm_roofline_pct", "peak"),
    ("moe_peak_load_ratio", "counters")])
def test_a_reader_that_finds_nothing_returns_none(spec, name, lacks,
                                                  monkeypatch):
    """As on the parent commit (no counter, no scope: a trace that names other
    scopes gives None, and does not raise), on a rehearsal (no peak, no device
    plane) and for a reference module with no counts of its own."""
    files = harness.Files(spec, [DATA])
    files.load_module("references", REAL)
    files.load_module("references", "toy_gpt2")
    read = files.load_module("metrics", name).read
    ctx = _ctx({"reference": "toy_gpt2" if lacks == "counts" else REAL})
    if lacks == "counters":
        ctx["counters"] = {"steps": 100}
    if lacks == "peak":
        ctx["peak"] = None
    if lacks == "scope":
        d = json.load(open(DATA + "/trace_scoped.json"))
        monkeypatch.setattr(trace_scopes, "program_names",
                            lambda: d["op_names_serve"])
        ctx["planes"] = d["planes"]
    assert read(ctx) is None


def test_the_parent_fails_at_once_on_the_new_cell(spec, monkeypatch):
    """A program without the expert family (the parent commit, with these
    benchmark files laid over it): the driver's first import fails, before any
    weight is made."""
    monkeypatch.setitem(sys.modules, "paddle_tpu.generation.moe_window", None)
    with pytest.raises(ImportError):
        rehearse(CELL, spec=spec)
