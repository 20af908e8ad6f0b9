"""A serve cell's list of requests (`pool`): the lists this PR lengthened
begin with the lists they were, and a list that runs dry fails the run."""
import json
import shutil

import numpy as np
import pytest

from helpers import DATA, SERVE, rehearse

from benchmark import harness, traffic


@pytest.mark.parametrize("cell,old_pool", [("gpt2_124m_chat_c32", 1024),
                                           ("ouro_2_6b_reason_c16", 512)])
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 36001])
def test_a_longer_pool_begins_with_the_pool_it_was(cell, old_pool, seed):
    """Lengths are drawn cycle by cycle and ids in one stream, so the traffic
    of the program that stands did not change with `pool` (PR 36): only where
    it ends."""
    files = harness.Files()
    wl = files.load_json("workloads", cell)
    cfg = files.load_json("configs", files.cell(cell)["config"])
    assert wl["pool"] >= 8 * old_pool
    new = traffic.requests(wl, cfg, seed)
    old = traffic.requests(dict(wl, pool=old_pool), cfg, seed)
    assert len(new) == wl["pool"] and len(old) == old_pool
    for (p, n), (q, m) in zip(new, old):
        assert n == m and p.dtype == q.dtype and np.array_equal(p, q)


def test_a_pool_that_runs_dry_fails_the_run(tmp_path):
    """The toy cell's own pool outlasts its window (test_rehearsal.py); with
    a list its clients finish inside the window the run raises, names `pool`
    and returns nothing."""
    d = tmp_path / "data"
    shutil.copytree(DATA, d)
    p = d / "workloads" / (SERVE + ".json")
    wl = json.loads(p.read_text())
    wl["pool"] = 4 * wl["clients"]
    p.write_text(json.dumps(wl))
    with pytest.raises(harness.BenchError, match="all 16 requests.*Raise `pool`"):
        rehearse(SERVE, data_dirs=[str(d)])
    r = rehearse(SERVE)
    assert r["correct"] is True and r["attempted"] > 4 * wl["clients"]
