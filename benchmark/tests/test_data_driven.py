"""A configuration, a cell and a per-layer metric added as NEW files are found
with no edit to a file that is there."""
import hashlib
import json
import os

from helpers import DATA, SPEC, rehearse

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _digest():
    h = hashlib.sha256()
    for d, dirs, fs in sorted(os.walk(BENCH)):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for f in sorted(fs):
            if f.endswith(".pyc"):
                continue
            p = os.path.join(d, f)
            h.update(p.encode() + open(p, "rb").read())
    return h.hexdigest()


def test_new_files_are_found_by_name(tmp_path):
    before = _digest()
    new = tmp_path / "added"
    for sub in ("configs", "workloads", "metrics"):
        (new / sub).mkdir(parents=True)
    # a new configuration: the toy decoder, one layer deeper, the same driver
    # and reference named in data
    cfg = json.load(open(os.path.join(DATA, "configs", "toy_gpt2.json")))
    cfg["n_layer"] = 3
    (new / "configs" / "toy_gpt2_deep.json").write_text(json.dumps(cfg))
    # a new cell: another traffic mix for it, parameters only
    wl = json.load(open(os.path.join(DATA, "workloads",
                                     "toy_gpt2_chat_c4.json")))
    wl.update(clients=2, prompt_len={"dist": "loguniform", "min": 6, "max": 12})
    (new / "workloads" / "toy_gpt2_deep_short_c2.json").write_text(
        json.dumps(wl))
    # a new per-layer metric: a small reader of its own
    (new / "metrics" / "steps_per_request.py").write_text(
        "def read(ctx):\n"
        "    c = ctx['counters']\n"
        "    return c['steps'] / len(c['finished']) if c.get('finished') "
        "else None\n")
    spec = json.load(open(SPEC))
    spec["configs"].append({"name": "toy_gpt2_deep", "source": "toy",
                            "file": "x", "reduced": [], "why": "test"})
    cell = "toy_gpt2_deep_short_c2"
    spec["workloads"].append({"name": cell, "config": "toy_gpt2_deep",
                              "traffic": "short_c2", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "toy_gpt2_chat_c4" in m.get("workloads", []):
            m["workloads"].append(cell)
    spec["per_layer"].append({
        "name": "steps_per_request.serve", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "entry points",
        "moves": "serve_out_tokens_per_s", "workloads": [cell]})
    spec_path = tmp_path / "BENCHMARK.json"
    spec_path.write_text(json.dumps(spec))

    r = rehearse(cell, trace=1, spec=str(spec_path),
                 data_dirs=[str(new), DATA])
    assert r["correct"] is True
    assert r["metrics"]["steps_per_request.serve"]["value"] > 1
    assert "engine_step_ms.serve" in r["metrics"]
    # an old cell does not report the new metric
    r = rehearse("toy_gpt2_chat_c4", trace=1, spec=str(spec_path),
                 data_dirs=[str(new), DATA])
    assert "steps_per_request.serve" not in r["metrics"]
    assert _digest() == before


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    from benchmark import harness
    files = harness.Files(SPEC, [DATA])
    for name in ("mfu_pct", "hbm_peak_pct", "device_idle_pct",
                 "flash_attn_roofline", "engine_step_ms", "slot_fill_pct",
                 "ttft_p90_ms"):
        read = files.load_module("metrics", name).read
        ctx = {"counters": {}, "config": {}, "workload": {"kind": "train"},
               "end_to_end": {},
               "peak": None, "window_s": 1.0, "cell": {"chips": 1},
               "memory_peak_bytes": 0, "trace": None, "planes": None,
               "notes": {}, "window_compiles": 0}
        assert read(ctx) is None, name
