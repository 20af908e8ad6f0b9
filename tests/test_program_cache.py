"""Persistent AOT program cache (core/program_cache.py) tests.

Covers the ISSUE-1 tentpole + satellites: stable program fingerprints,
the lowering-flag snapshot in the in-memory Executor cache key (the
stale-executable bugfix), the LRU capacity bound, disk trace-cache
hit/miss with bitwise-identical fetches, corruption/truncation/version
-skew fallback to a clean recompile, cross-process reuse through
subprocesses, Predictor wiring, and the bench.py `compile` block.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import program_cache
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def flag_guard():
    from paddle_tpu import flags as F
    saved = dict(F._values)
    yield
    F._values.clear()
    F._values.update(saved)


@pytest.fixture(scope="module")
def cache_root(tmp_path_factory):
    # module-scoped: jax's persistent compilation cache dir is pointed
    # here once and pytest keeps the dir for the whole session
    return str(tmp_path_factory.mktemp("aot_cache"))


def _build(width=12, hidden=24, with_opt=True):
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [width])
        h = layers.fc(x, hidden, act="relu")
        loss = layers.mean(h)
        if with_opt:
            pt.optimizer.SGD(0.1).minimize(loss, startup_program=startup,
                                           program=main)
    return main, startup, loss


def _run_fresh(main, startup, loss, feed, cache_dir=None,
               use_program_cache=True):
    """Fresh Executor + fresh Scope: init, one train step, fetch."""
    exe = pt.Executor(program_cache_dir=cache_dir)
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    return exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope,
                   use_program_cache=use_program_cache)


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------
def test_fingerprint_stable_and_sensitive(flag_guard):
    m1, _, _ = _build()
    m2, _, _ = _build()
    sig = (("x", (4, 12), "float32"),)
    fp1 = m1.fingerprint(sig, ("loss",), ())
    assert fp1 and fp1 == m2.fingerprint(sig, ("loss",), ())
    # op attr change -> new fingerprint
    m2.global_block.ops[0].attrs["_salt"] = 1
    assert m2.fingerprint(sig, ("loss",), ()) != fp1
    # feed signature is pinned
    assert m1.fingerprint((("x", (8, 12), "float32"),), ("loss",), ()) \
        != fp1
    # lowering-relevant flag is pinned
    pt.set_flags({"FLAGS_dropout_storage": "u8"})
    assert m1.fingerprint(sig, ("loss",), ()) != fp1


def test_fingerprint_ndarray_attr_no_collision():
    m1, _, _ = _build()
    m2, _, _ = _build()
    # large ndarray attrs hash by content — numpy's elided repr must
    # never make two different programs collide
    a = np.arange(10000, dtype=np.float32)
    b = a.copy()
    b[7777] = -1.0
    m1.global_block.ops[0].attrs["table"] = a
    m2.global_block.ops[0].attrs["table"] = b
    assert m1.fingerprint() != m2.fingerprint()


# ---------------------------------------------------------------------------
# in-memory cache: flag snapshot in the key (stale-executable bugfix)
# ---------------------------------------------------------------------------
def test_inmemory_key_snapshots_lowering_flags(flag_guard):
    main, startup, loss = _build()
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((4, 12), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    before = stat_get("STAT_executor_compile")
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert stat_get("STAT_executor_compile") == before  # cached
    # flipping a lowering-relevant flag must MISS (previously returned
    # the stale pre-flip executable)
    pt.set_flags({"FLAGS_embedding_onehot_grad": False})
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert stat_get("STAT_executor_compile") == before + 1
    # flipping back returns to the still-cached original entry
    pt.set_flags({"FLAGS_embedding_onehot_grad": True})
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert stat_get("STAT_executor_compile") == before + 1


def test_executor_cache_lru_capacity(flag_guard):
    pt.set_flags({"FLAGS_executor_cache_capacity": 2})
    main, startup, loss = _build()
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    evict0 = stat_get("STAT_executor_cache_evict")
    for b in (2, 3, 4, 5):
        exe.run(main, feed={"x": np.ones((b, 12), np.float32)},
                fetch_list=[loss.name], scope=scope)
    assert len(exe._cache) <= 2
    assert stat_get("STAT_executor_cache_evict") > evict0
    # the evicted batch=2 entry recompiles cleanly
    before = stat_get("STAT_executor_compile")
    exe.run(main, feed={"x": np.ones((2, 12), np.float32)},
            fetch_list=[loss.name], scope=scope)
    assert stat_get("STAT_executor_compile") == before + 1


# ---------------------------------------------------------------------------
# disk trace cache
# ---------------------------------------------------------------------------
def test_disk_cache_hit_bitwise_identical(cache_root):
    main, startup, loss = _build()
    feed = {"x": np.ones((4, 12), np.float32)}
    miss0 = stat_get("STAT_program_cache_trace_miss")
    out_cold = _run_fresh(main, startup, loss, feed, cache_dir=cache_root)
    assert stat_get("STAT_program_cache_trace_miss") > miss0
    assert stat_get("STAT_program_cache_bytes_written") > 0

    hit0 = stat_get("STAT_program_cache_trace_hit")
    out_warm = _run_fresh(main, startup, loss, feed, cache_dir=cache_root)
    assert stat_get("STAT_program_cache_trace_hit") > hit0
    assert out_cold[0].tobytes() == out_warm[0].tobytes()

    # an uncached run (disk cache off AND use_program_cache=False, the
    # plain jit path) produces the same bits
    out_plain = _run_fresh(main, startup, loss, feed, cache_dir="",
                           use_program_cache=False)
    assert out_plain[0].tobytes() == out_cold[0].tobytes()


def test_use_program_cache_false_bypasses_disk(cache_root):
    main, startup, loss = _build(width=13)  # unique program for stats
    feed = {"x": np.ones((4, 13), np.float32)}
    miss0 = stat_get("STAT_program_cache_trace_miss")
    hit0 = stat_get("STAT_program_cache_trace_hit")
    exe = pt.Executor(program_cache_dir=cache_root)
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_program_cache=False)
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope,
            use_program_cache=False)
    assert stat_get("STAT_program_cache_trace_miss") == miss0
    assert stat_get("STAT_program_cache_trace_hit") == hit0


def _trace_entries(cache_root):
    d = os.path.join(cache_root, "trace")
    if not os.path.isdir(d):
        return []
    return sorted(os.path.join(d, f) for f in os.listdir(d)
                  if f.endswith(".stablehlo"))


_DAMAGES = ["garbage", "truncate", "version"]


@pytest.mark.parametrize("damage", _DAMAGES)
def test_damaged_entry_falls_back_and_heals(cache_root, damage):
    width = 30 + _DAMAGES.index(damage)  # unique program per case
    main, startup, loss = _build(width=width)
    feed = {"x": np.ones((4, width), np.float32)}
    before = set(_trace_entries(cache_root))
    out_cold = _run_fresh(main, startup, loss, feed, cache_dir=cache_root)
    # damage only THIS program's entries; the shared dir holds healthy
    # entries from other tests
    entries = sorted(set(_trace_entries(cache_root)) - before)
    assert entries
    for path in entries:
        if damage == "garbage":
            with open(path, "wb") as f:
                f.write(b"\x00garbage\xff" * 7)
        elif damage == "truncate":
            blob = open(path, "rb").read()
            with open(path, "wb") as f:
                f.write(blob[:10])
        else:  # valid container, wrong jax version in the header
            blob = open(path, "rb").read()
            rest = blob[len(program_cache.MAGIC):]
            nl = rest.index(b"\n")
            hdr = json.loads(rest[:nl])
            hdr["jax"] = "0.0.0"
            with open(path, "wb") as f:
                f.write(program_cache.MAGIC +
                        json.dumps(hdr, sort_keys=True).encode() + b"\n" +
                        rest[nl + 1:])
    corrupt0 = stat_get("STAT_program_cache_corrupt")
    out_recover = _run_fresh(main, startup, loss, feed,
                             cache_dir=cache_root)
    assert stat_get("STAT_program_cache_corrupt") > corrupt0
    assert out_recover[0].tobytes() == out_cold[0].tobytes()
    # the bad entries were overwritten with good ones: next run hits
    hit0 = stat_get("STAT_program_cache_trace_hit")
    corrupt1 = stat_get("STAT_program_cache_corrupt")
    out_warm = _run_fresh(main, startup, loss, feed, cache_dir=cache_root)
    assert stat_get("STAT_program_cache_trace_hit") > hit0
    assert stat_get("STAT_program_cache_corrupt") == corrupt1
    assert out_warm[0].tobytes() == out_cold[0].tobytes()


def test_int64_feed_warm_hit_no_corrupt(cache_root):
    # jit canonicalizes int64 feeds to int32 (x64 off): the stored
    # in_avals must compare equal to our avals or every warm process
    # with an int64 feed pays a spurious corrupt + re-export
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.data("y", [1], dtype="int64")
        logits = layers.fc(x, 10)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, y))
    feed = {"x": np.ones((8, 4), np.float32),
            "y": np.zeros((8, 1), np.int64)}
    out_cold = _run_fresh(main, startup, loss, feed, cache_dir=cache_root)
    corrupt0 = stat_get("STAT_program_cache_corrupt")
    hit0 = stat_get("STAT_program_cache_trace_hit")
    out_warm = _run_fresh(main, startup, loss, feed, cache_dir=cache_root)
    assert stat_get("STAT_program_cache_corrupt") == corrupt0
    assert stat_get("STAT_program_cache_trace_hit") > hit0
    assert out_cold[0].tobytes() == out_warm[0].tobytes()


# ---------------------------------------------------------------------------
# mesh-aware fingerprints (ISSUE-6 satellite: the mesh topology — axis
# names+sizes+device kind — rides in the in-memory key and the disk
# fingerprint, so entries never cross topologies)
# ---------------------------------------------------------------------------
@pytest.mark.spmd
def test_inmemory_key_includes_mesh_topology():
    from paddle_tpu.mesh import ShardingPlan, use_plan
    main, startup, loss = _build(width=18)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((8, 18), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    before = stat_get("STAT_executor_compile")
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert stat_get("STAT_executor_compile") == before  # cached
    # same Executor under a dp4xmp2 plan: MISS (an executable
    # partitioned for one topology must never serve another)
    plan = ShardingPlan("dp4xmp2")
    with use_plan(plan):
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        assert stat_get("STAT_executor_compile") == before + 1
        # identical mesh: steady state, no recompiles
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        assert stat_get("STAT_executor_compile") == before + 1
    # chip-count flip (dp8): its own entry, never the dp4xmp2 one
    with use_plan(ShardingPlan("dp8")):
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert stat_get("STAT_executor_compile") == before + 2


@pytest.mark.spmd
def test_disk_cache_mesh_topology_round_trip(cache_root):
    """1-device and dp4xmp2 runs of the SAME program get distinct disk
    entries; an identical mesh in a fresh Executor hits its entry; a
    chip-count change misses (stale executables are structurally
    impossible — the topology is inside the fingerprint)."""
    from paddle_tpu.mesh import ShardingPlan, use_plan
    main, startup, loss = _build(width=19)
    feed = {"x": np.ones((8, 19), np.float32)}

    out_single = _run_fresh(main, startup, loss, feed,
                            cache_dir=cache_root)
    # dp4xmp2: same program + feed, distinct fingerprint -> trace MISS
    plan = ShardingPlan("dp4xmp2")
    miss0 = stat_get("STAT_program_cache_trace_miss")
    with use_plan(plan):
        out_mesh = _run_fresh(main, startup, loss, feed,
                              cache_dir=cache_root)
    assert stat_get("STAT_program_cache_trace_miss") > miss0
    # partitioned numerics match the single-device run
    np.testing.assert_allclose(np.asarray(out_mesh[0]),
                               np.asarray(out_single[0]),
                               rtol=1e-5, atol=1e-6)
    # identical mesh, fresh Executor/Scope: disk AOT HIT, same bits
    hit0 = stat_get("STAT_program_cache_trace_hit")
    with use_plan(plan):
        out_warm = _run_fresh(main, startup, loss, feed,
                              cache_dir=cache_root)
    assert stat_get("STAT_program_cache_trace_hit") > hit0
    assert out_warm[0].tobytes() == out_mesh[0].tobytes()
    # chip-count change (dp8): never served a dp4xmp2 entry
    miss1 = stat_get("STAT_program_cache_trace_miss")
    hit1 = stat_get("STAT_program_cache_trace_hit")
    with use_plan(ShardingPlan("dp8")):
        out_dp8 = _run_fresh(main, startup, loss, feed,
                             cache_dir=cache_root)
    assert stat_get("STAT_program_cache_trace_miss") > miss1
    assert stat_get("STAT_program_cache_trace_hit") == hit1
    np.testing.assert_allclose(np.asarray(out_dp8[0]),
                               np.asarray(out_single[0]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# cross-process reuse (satellite: subprocess A populates, B hits)
# ---------------------------------------------------------------------------
_XPROC = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as pt
from paddle_tpu import layers

cache_dir, out_npy = sys.argv[1], sys.argv[2]
pt.set_flags({"FLAGS_program_cache_dir": cache_dir})
main, startup = pt.Program(), pt.Program()
with pt.program_guard(main, startup):
    x = layers.data("x", [10])
    h = layers.fc(x, 20, act="relu")
    loss = layers.mean(h)
    pt.optimizer.SGD(0.1).minimize(loss, startup_program=startup,
                                   program=main)
exe = pt.Executor()
exe.run(startup)
out = exe.run(main, feed={"x": np.ones((3, 10), np.float32)},
              fetch_list=[loss.name])
np.save(out_npy, out[0])
from paddle_tpu.monitor import get_float_stats
st = get_float_stats()
print(json.dumps({"hit": st.get("STAT_program_cache_trace_hit", 0),
                  "miss": st.get("STAT_program_cache_trace_miss", 0)}))
"""


def _spawn_xproc(cache_dir, out_npy, tmp):
    script = os.path.join(tmp, "xproc.py")
    with open(script, "w") as f:
        f.write(_XPROC)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, script, cache_dir, out_npy],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cross_process_reuse(tmp_path):
    tmp = str(tmp_path)
    cache_dir = os.path.join(tmp, "aot")
    a = _spawn_xproc(cache_dir, os.path.join(tmp, "a.npy"), tmp)
    b = _spawn_xproc(cache_dir, os.path.join(tmp, "b.npy"), tmp)
    assert a["hit"] == 0 and a["miss"] > 0      # A populated
    assert b["hit"] > 0 and b["miss"] == 0      # B reused the traces
    # uncached process ("" disables the disk cache)
    c = _spawn_xproc("", os.path.join(tmp, "c.npy"), tmp)
    assert c["hit"] == 0 and c["miss"] == 0
    va = np.load(os.path.join(tmp, "a.npy"))
    vb = np.load(os.path.join(tmp, "b.npy"))
    vc = np.load(os.path.join(tmp, "c.npy"))
    assert va.tobytes() == vb.tobytes() == vc.tobytes()


# ---------------------------------------------------------------------------
# where the caches live (PR 22)
# ---------------------------------------------------------------------------
_CACHE_RULE = """
import json, os, sys
import jax
from paddle_tpu import serving_core
from paddle_tpu.core import program_cache
before = jax.config.jax_compilation_cache_dir
program_cache.ensure_xla_cache(sys.argv[1])
serving_core._maybe_enable_compile_cache()
print(json.dumps({"before": before,
                  "after": jax.config.jax_compilation_cache_dir,
                  "made": os.path.isdir(os.path.join(sys.argv[1], "xla"))}))
"""


def test_jax_compilation_cache_dir_env_is_left_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the program uses that
    directory and sets no other in code — neither the Executor's
    ensure_xla_cache nor the serving core's own switch."""
    theirs, ours = str(tmp_path / "theirs"), str(tmp_path / "ours")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=theirs,
               PADDLE_TPU_PROGRAM_CACHE_DIR=ours)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _CACHE_RULE, ours],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got == {"before": theirs, "after": theirs, "made": False}


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    """Without the env override the cache is at ONE fixed path inside
    the checkout — no home directory, pid, time or temporary name — and
    the framework-free serving core spells the same path."""
    from paddle_tpu import serving_core
    monkeypatch.delenv("PADDLE_TPU_PROGRAM_CACHE_DIR")
    want = os.path.join(REPO, ".paddle_tpu_cache", "aot")
    assert program_cache.default_dir() == want
    assert program_cache.resolve_dir() == want
    assert serving_core.default_cache_dir() == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".paddle_tpu_cache/" in f.read().split()


def test_framework_token_hashes_contents_not_mtimes(tmp_path):
    """Two copies of one tree with different mtimes share a token (a
    fresh checkout must hit what the old one wrote); an edit changes
    it."""
    import shutil
    a, b = tmp_path / "a", tmp_path / "b"
    (a / "sub").mkdir(parents=True)
    (a / "x.py").write_text("x = 1\n")
    (a / "sub" / "y.py").write_text("y = 2\n")
    (a / "notes.txt").write_text("not source\n")
    shutil.copytree(a, b)
    for dirpath, _, files in os.walk(b):
        for fn in files:
            os.utime(os.path.join(dirpath, fn), (1, 1))
    tok = program_cache.source_tree_token(str(a))
    assert program_cache.source_tree_token(str(b)) == tok
    (b / "notes.txt").write_text("still not source\n")
    assert program_cache.source_tree_token(str(b)) == tok
    (b / "sub" / "y.py").write_text("y = 3\n")
    assert program_cache.source_tree_token(str(b)) != tok


# ---------------------------------------------------------------------------
# Predictor wiring
# ---------------------------------------------------------------------------
def test_predictor_program_cache(cache_root, tmp_path):
    from paddle_tpu import layers
    from paddle_tpu.inference import Config, create_predictor
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [6])
        pred = layers.fc(x, 3)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        d = str(tmp_path / "model")
        pt.save_inference_model(d, ["x"], [pred], exe, main)

    xb = np.random.RandomState(0).randn(5, 6).astype(np.float32)

    def serve():
        cfg = Config(model_dir=d)
        cfg.enable_program_cache(cache_root)
        p = create_predictor(cfg)
        return p.run([xb])[0]

    miss0 = stat_get("STAT_program_cache_trace_miss")
    out1 = serve()
    assert stat_get("STAT_program_cache_trace_miss") > miss0
    hit0 = stat_get("STAT_program_cache_trace_hit")
    out2 = serve()
    assert stat_get("STAT_program_cache_trace_hit") > hit0
    assert out1.tobytes() == out2.tobytes()
    # disable_program_cache really opts out
    cfg = Config(model_dir=d)
    cfg.disable_program_cache()
    miss1 = stat_get("STAT_program_cache_trace_miss")
    hit1 = stat_get("STAT_program_cache_trace_hit")
    create_predictor(cfg).run([xb])
    assert stat_get("STAT_program_cache_trace_miss") == miss1
    assert stat_get("STAT_program_cache_trace_hit") == hit1


# ---------------------------------------------------------------------------
# bench.py `compile` block (cold/warm in subprocesses)
# ---------------------------------------------------------------------------
def _load_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "pt_bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_compile_block_small(monkeypatch):
    # tiny shape: validates the block's plumbing (subprocess pair, hit
    # flag, bitwise fetch check) without the 12-layer compile cost
    monkeypatch.setenv("PT_COMPILE_BENCH_LAYERS_N", "2")
    monkeypatch.setenv("PT_COMPILE_BENCH_H", "64")
    monkeypatch.setenv("PT_COMPILE_BENCH_FF", "128")
    monkeypatch.setenv("PT_COMPILE_BENCH_HEADS", "4")
    monkeypatch.setenv("PT_COMPILE_BENCH_S", "16")
    monkeypatch.setenv("PT_COMPILE_BENCH_B", "2")
    block = _load_bench().bench_compile()
    assert "error" not in block, block
    assert block["warm_trace_cache_hit"] is True
    assert block["fetch_bitwise_identical"] is True
    assert block["cold_compile_s"] > 0 and block["warm_compile_s"] > 0


@pytest.mark.slow
def test_cold_warm_speedup_bert12_acceptance():
    """ISSUE-1 acceptance: warm-start Executor.run of the 12-layer
    BERT-shaped static train step reaches first results >= 3x faster
    than cold start on CPU, with bitwise-identical fetches."""
    block = _load_bench().bench_compile()
    assert "error" not in block, block
    assert block["warm_trace_cache_hit"] is True
    assert block["fetch_bitwise_identical"] is True
    assert block["speedup"] >= 3.0, block
