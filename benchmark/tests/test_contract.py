"""BENCHMARK.json against the letter of the benchmark's contract, and against
the files it names."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _spec():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_and_limits():
    s = _spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    assert 1 <= len(s["paths"]) <= 16 and len(s["command"]) <= 32
    assert all(_line(w) for w in s["command"])
    assert 1 <= len(s["configs"]) <= 24 and 1 <= len(s["workloads"]) <= 24
    assert 1 <= len(s["end_to_end"]) <= 16 and 1 <= len(s["per_layer"]) <= 128
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in s["paths"])
        json.load(open(os.path.join(ROOT, c["file"])))
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in {c["name"] for c in s["configs"]}
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in s[group]:
            assert set(m) - {"workloads"} == keys, m["name"]
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    assert len({w["name"] for w in s["workloads"]}) == len(s["workloads"])
    assert len({(w["config"], w["traffic"]) for w in s["workloads"]}) == \
        len(s["workloads"])
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(set(names)) == len(names)
    assert {c["name"] for c in s["configs"]} == \
        {w["config"] for w in s["workloads"]}
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 4)


def test_metrics_bounds_and_arrows():
    s = _spec()
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells

    def reports(m, cell):
        return cell in m.get("workloads", cells)
    for m in s["per_layer"]:
        assert _line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(e2e[m["moves"]], cell), m["name"]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        assert sum(reports(m, cell) for m in s["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in s["per_layer"])
        # a kernel's roofline is bounded by the whole step's mfu on the same
        # end-to-end metric
        for m in s["per_layer"]:
            if "roofline" in m["name"] and reports(m, cell):
                assert any("mfu" in re.split(r"[_.]", o["name"])
                           and o["moves"] == m["moves"] and reports(o, cell)
                           for o in s["per_layer"])


def test_every_name_has_its_files():
    from benchmark import harness
    s = _spec()
    files = harness.Files()
    for w in s["workloads"]:
        wl = files.load_json("workloads", w["name"])
        cfg = files.load_json("configs", w["config"])
        assert wl["kind"] in ("train", "serve")
        for kind, key in (("drivers", "driver"), ("references", "reference")):
            assert os.path.isfile(files.find(kind, cfg[key], ".py"))
        assert cfg["precision"] and cfg["source"].startswith("https://")
    for m in s["per_layer"]:
        files.find("metrics", m["name"].partition(".")[0], ".py")
    full = 2 + 14 * 24
    assert full * (s["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
