"""The harness end to end for both kinds of cell, on the CPU at toy sizes."""
import json

import pytest

from helpers import DATA, SERVE, TRAIN, rehearse

LARGE_SEED = 2 ** 31 + 12345     # the driver's seeds do not fit 32 signed bits


@pytest.mark.parametrize("cell,metrics", [
    (TRAIN, {"train_tokens_per_s", "setup_s"}),
    (SERVE, {"serve_out_tokens_per_s", "tpot_p90_ms", "setup_s"})])
def test_run_is_correct_and_prints_no_result_line(cell, metrics, capsys):
    r = rehearse(cell, seed=LARGE_SEED)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == metrics
    assert list(r)[-1] == "compared"
    for v in r["compared"].values():
        assert v["value"] <= v["limit"]
    out = capsys.readouterr()
    # a rehearsal never prints a result under a device metric's name
    assert "metrics" not in out.out
    # every number compared is on stderr beside its limit
    for name in r["compared"]:
        assert "compared %s" % name in out.err
    json.dumps(r)


@pytest.mark.parametrize("cell,expect", [
    (TRAIN, {"window_compiles.train"}),
    (SERVE, {"window_compiles.serve", "engine_step_ms.serve",
             "slot_fill_pct.serve", "ttft_p90_ms.serve"})])
def test_traced_run_reports_the_per_layer_metrics(cell, expect):
    r = rehearse(cell, trace=1)
    assert set(r["metrics"]) == expect
    assert r["metrics"]["window_compiles." + cell_kind(cell)]["value"] == 0
    if cell == SERVE:
        assert 0 < r["metrics"]["slot_fill_pct.serve"]["value"] <= 100


def cell_kind(cell):
    return "train" if cell == TRAIN else "serve"


def test_serve_rate_counts_every_token_that_reached_the_host(monkeypatch):
    """tokens/s x window is the engine's own count of the tokens it handed
    out inside the window, whole requests or not."""
    from benchmark import harness
    from helpers import SPEC
    mod = harness.Files(SPEC, [DATA]).load_module("drivers", "generation_pool")
    real, seen = mod.Driver.window, {}

    def window(self, seconds):
        seen.update(real(self, seconds))
        return seen
    monkeypatch.setattr(mod.Driver, "window", window)
    rehearse(SERVE)
    counted = seen["end_to_end"]["serve_out_tokens_per_s"] * seen["window_s"]
    assert counted > 0
    # the two counts are read a few microseconds apart: at most one step's
    # tokens (one a client) lie between them
    assert abs(counted - seen["counters"]["tokens"]) <= 4 + 1e-6


def test_same_seed_same_inputs_other_seed_same_sizes_in_the_same_order():
    from benchmark import traffic
    wl = json.load(open(DATA + "/workloads/%s.json" % SERVE))
    cfg = {"vocab_size": 128}
    a, b, c = (traffic.requests(wl, cfg, s) for s in (5, 5, 6))
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert [len(x[0]) for x in a] == [len(x[0]) for x in c]
    assert any((x[0] != y[0]).any() for x, y in zip(a, c))
    n = wl["cycle"]
    lens = [len(x[0]) for x in a]
    assert sorted(lens[:n]) == sorted(lens[n:2 * n]) and lens[:n] != lens[n:2 * n]


def test_a_train_cell_that_left_its_kernel_does_not_report(tmp_path):
    """The toy cell traces `composed`; told to stand for `flash` it fails."""
    import shutil
    from benchmark import harness
    d = tmp_path / "data"
    shutil.copytree(DATA, d)
    p = d / "workloads" / (TRAIN + ".json")
    wl = json.loads(p.read_text())
    wl["expect_attention"], wl["expect_dropout"] = "flash", "inkernel"
    p.write_text(json.dumps(wl))
    with pytest.raises(harness.BenchError, match="traced attention"):
        rehearse(TRAIN, data_dirs=[str(d)])


def test_no_accelerator_is_an_error(capsys):
    from benchmark import run
    from helpers import SPEC
    rc = run.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"],
                  spec_path=SPEC, data_dirs=[DATA])
    assert rc == 1
    assert capsys.readouterr().out == ""
