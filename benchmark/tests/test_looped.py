"""The looped family's driver, reference and three readers, rehearsed on the CPU
at toy sizes (`data/configs/toy_ouro.json`), with a spec built here: the
recorded `data/BENCHMARK.json` is left as it is."""
import json
import sys

import numpy as np
import pytest

from helpers import DATA, SPEC, rehearse

from benchmark import harness, trace_scopes

CELL, OLD = "toy_ouro_reason_c4", "toy_gpt2_chat_c4"
NEW_METRICS = ("config_mfu_pct", "step_hbm_roofline_pct", "loop_pass_device_ms")


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    s = json.load(open(SPEC))
    s["configs"].append({"name": "toy_ouro", "source": "toy", "file": "x",
                         "reduced": [], "why": "rehearsal"})
    s["workloads"].append({"name": CELL, "config": "toy_ouro",
                           "traffic": "reason_c4", "chips": 1,
                           "why": "rehearsal of the looped family"})
    for m in s["end_to_end"] + s["per_layer"]:
        if OLD in m.get("workloads", []):
            m["workloads"].append(CELL)
    for name, unit in zip(NEW_METRICS, ("%", "%", "ms")):
        s["per_layer"].append({
            "name": name + ".serve", "unit": unit, "better": "higher",
            "source": "device_trace", "layer": "model step",
            "moves": "serve_out_tokens_per_s", "workloads": [CELL]})
    p = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    p.write_text(json.dumps(s))
    return str(p)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_driver_serves_the_looped_family_and_is_correct(spec, trace):
    r = rehearse(CELL, seed=2 ** 31 + 77, trace=trace, spec=spec)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["served_logit_gap"]["value"] <= 1e-4
    if trace:
        # what a rehearsal can read: the counters' metrics. The three new
        # ones need a peak or a device plane and leave the line out here
        assert {"engine_step_ms.serve", "slot_fill_pct.serve",
                "window_compiles.serve"} <= set(r["metrics"])
        assert not set(r["metrics"]) & {n + ".serve" for n in NEW_METRICS}
        assert r["metrics"]["window_compiles.serve"]["value"] == 0
    else:
        assert set(r["metrics"]) == {"serve_out_tokens_per_s", "tpot_p90_ms",
                                     "setup_s"}


def test_the_driver_counts_attended_tokens_and_builds_the_family(spec,
                                                                 monkeypatch):
    files = harness.Files(spec, [DATA])
    mod = files.load_module("drivers", "generation_pool_source")
    seen = {}
    real = mod.Driver.window

    def window(self, seconds):
        out = real(self, seconds)
        seen.update(out["counters"], cfg=self.engine.cfg,
                    pools=self.engine.k_pools.shape)
        return out
    monkeypatch.setattr(mod.Driver, "window", window)
    rehearse(CELL, spec=spec)
    assert seen["attended_tokens"] > seen["tokens"] > 0
    assert type(seen["cfg"]).__name__ == "LoopedDecoderConfig"
    assert seen["cfg"].max_seq_len == 64
    # passes x layers of cache, rows of kv_heads x head_dim
    assert seen["pools"] == (6, 64, 16, 48)


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


@pytest.mark.parametrize("control", ["fp8", "int8"])
@pytest.mark.parametrize("seed", [3, 4])
def test_the_controls_are_not_correct(spec, seed, control):
    """Bfloat16 weights as the real cell's; the reference's own greedy choices
    are sound, the control's first choices lie outside the toy's limit by a
    hundred times and more."""
    R = harness.Files(spec, [DATA]).load_module("references", "ouro_2_6b")
    cfg = json.load(open(DATA + "/configs/toy_ouro.json"))
    w = R.make_weights(cfg, seed)
    ref = R.Reference(cfg, pad_to=64, new_tokens=32)
    rng = np.random.RandomState(seed)
    widest = 0.0
    for _ in range(4):
        prompt = rng.randint(0, cfg["vocab_size"], 24)
        tail = rng.randint(0, cfg["vocab_size"], 32).tolist()
        widest = max(widest, ref.gaps(w, prompt, tail, control=control).max())
    assert widest > 0.1


def _ctx(cfg, **kw):
    ctx = {"config": cfg, "cell": {"chips": 1}, "kind": "serve", "notes": {},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_s": 10.0, "planes": None, "trace": None,
           "counters": {"steps": 100, "attended_tokens": 300000,
                        "finished": [(64, 128)] * 12}}
    ctx.update(kw)
    return ctx


def test_the_counts_against_hand_counts():
    from benchmark.references import ouro_2_6b as R
    cfg = json.load(open(harness.HERE + "/configs/ouro_2_6b.json"))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert R._layer_matrix_params(cfg) == layer == 51_380_224
    n = 64 + 128 - 1
    want = (2 * layer * 192 * n + 4 * 2048 * 192 * (n * (n + 1) // 2)
            + 2 * 2048 * 49152 * 128)
    assert R.request_flops(cfg, 64, 128) == want
    # a step: the stack four times and the head, 19.93 GB; a token attended
    # is 1.5 MiB of K and V
    assert R.step_bytes(cfg, 1, 0) == (layer * 192 + 2048 * 49152) * 2
    assert abs(R.step_bytes(cfg, 1, 0) / 1e9 - 19.93) < 0.01
    assert R.step_bytes(cfg, 0, 1) == 1.5 * 2 ** 20
    assert R.step_bytes(cfg, 3, 7) == 3 * R.step_bytes(cfg, 1, 0) \
        + 7 * R.step_bytes(cfg, 0, 1)


def test_the_readers_read_the_configurations_own_counts(spec, monkeypatch):
    files = harness.Files(spec, [DATA])
    cfg = json.load(open(harness.HERE + "/configs/ouro_2_6b.json"))
    R = files.load_module("references", "ouro_2_6b")
    mfu = files.load_module("metrics", "config_mfu_pct").read
    got = mfu(_ctx(cfg))
    assert got == pytest.approx(
        100 * 12 * R.request_flops(cfg, 64, 128) / (10.0 * 197e12))
    assert 0 < got < 100
    # the roofline share: a recorded trace whose step's program ran twice,
    # 1000 ns each
    d = json.load(open(DATA + "/trace_scoped.json"))
    mod = next(iter(d["op_names_serve"]))
    for l in d["planes"][0]["lines"]:
        if l["name"] == "XLA Modules":
            for e in l["events"]:
                e[0] = e[0].replace("jit_step", mod)
    monkeypatch.setattr(trace_scopes, "program_names",
                        lambda: d["op_names_serve"])
    roof = files.load_module("metrics", "step_hbm_roofline_pct").read
    ctx = _ctx(cfg, planes=d["planes"])
    got = roof(ctx)
    note = ctx["notes"]["step_hbm_roofline"]
    assert note["bytes_a_step"] == R.step_bytes(cfg, 100, 300000) / 100
    assert got == pytest.approx(100 * note["bytes_a_step"]
                                / (note["device_s_a_step"] * 819e9))
    assert note["device_s_a_step"] > 0


@pytest.mark.parametrize("name,lacks", [
    ("config_mfu_pct", "counters"), ("config_mfu_pct", "counts"),
    ("config_mfu_pct", "peak"), ("step_hbm_roofline_pct", "trace"),
    ("step_hbm_roofline_pct", "counters"), ("step_hbm_roofline_pct", "counts"),
    ("step_hbm_roofline_pct", "peak"), ("loop_pass_device_ms", "trace")])
def test_a_reader_that_finds_nothing_returns_none(spec, name, lacks):
    """As on the parent commit (no counter, no scope), on a rehearsal (no peak,
    no device plane) and for a reference module with no counts of its own."""
    files = harness.Files(spec, [DATA])
    files.load_module("references", "ouro_2_6b")
    files.load_module("references", "toy_gpt2")
    read = files.load_module("metrics", name).read
    ctx = _ctx({"reference": "toy_gpt2" if lacks == "counts"
                else "ouro_2_6b"})
    if lacks == "counters":
        ctx["counters"] = {}
    if lacks == "peak":
        ctx["peak"] = None
    assert read(ctx) is None


def test_the_parent_fails_at_once_on_the_new_cell(spec, monkeypatch):
    """A program without the looped family (the parent commit, with these
    benchmark files laid over it): the driver's first import fails, before any
    weight is made."""
    monkeypatch.setitem(sys.modules, "paddle_tpu.generation.looped", None)
    with pytest.raises(ImportError):
        rehearse(CELL, spec=spec)
