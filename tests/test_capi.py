"""Inference C API: build csrc/capi.cc, serve an export_serialized()
artifact from a PURE C client (no Python host), compare against the
Python SerializedPredictor on the same feeds.

Parity target: the reference's inference C API + non-Python clients
(/root/reference/paddle/fluid/inference/capi/c_api.cc:1,
/root/reference/go/paddle/predictor.go:1).
"""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")


def _embed_flags():
    """Include/link flags for embedding THE RUNNING interpreter (a bare
    python3-config could describe a different install than the venv
    running the tests)."""
    import sysconfig
    inc = ["-I" + sysconfig.get_path("include")]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    ld = ["-L" + libdir, "-Wl,-rpath," + libdir, "-lpython" + ver,
          "-ldl", "-lm"]
    return inc, ld


@pytest.fixture(scope="module")
def capi_build(tmp_path_factory):
    if shutil.which("g++") is None or shutil.which("gcc") is None:
        pytest.skip("no C toolchain")
    d = tmp_path_factory.mktemp("capi")
    so = str(d / "libptcapi.so")
    exe = str(d / "client")
    inc, ld = _embed_flags()
    subprocess.run(["g++", "-O2", "-shared", "-fPIC",
                    os.path.join(CSRC, "capi.cc"), "-o", so, *inc, *ld],
                   check=True, capture_output=True)
    subprocess.run(["gcc", "-O2", os.path.join(CSRC, "capi_client_demo.c"),
                    "-o", exe, "-I", CSRC, "-L", str(d), "-lptcapi",
                    "-Wl,-rpath," + str(d), *ld],
                   check=True, capture_output=True)
    return so, exe


def _make_artifact(tmp_path):
    main, startup = pt.Program(), pt.Program()
    rng = np.random.RandomState(3)
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [4])
        h = pt.layers.fc(x, 8, act="relu")
        pred = pt.layers.fc(h, 3, name="cpred")
    exe = pt.Executor()
    exe.run(startup)
    d = str(tmp_path / "m")
    pt.save_inference_model(d, ["x"], [pred], exe, main)
    from paddle_tpu.inference import Config, create_predictor
    predictor = create_predictor(Config(model_dir=d))
    xb = (0.01 * np.arange(4, dtype=np.float32)).reshape(1, 4)
    art = str(tmp_path / "art")
    predictor.export_serialized(art, [xb])
    expect, = predictor.run([xb])
    return art, xb, np.asarray(expect)


def test_c_client_matches_python_predictor(capi_build, tmp_path):
    _, client = capi_build
    art, xb, expect = _make_artifact(tmp_path)
    assert os.path.exists(os.path.join(art, "serving_core.py"))
    # run the pure-C client WITHOUT the repo on the path: only libpython
    # + the artifact, pinned to the CPU through its environment
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [client, art, "4"] + ["%.6f" % v for v in xb.ravel()],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, (proc.stdout[-1000:], proc.stderr[-2000:])
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("inputs=1 outputs=1")
    out_line = [l for l in lines if l.startswith("OUT 0")][0]
    # "OUT 0 dtype=0 ndim=2 shape=1x3 : v v v"
    assert "dtype=0" in out_line and "shape=1x3" in out_line
    vals = np.array([float(v) for v in out_line.split(":")[1].split()],
                    np.float32)
    np.testing.assert_allclose(vals, expect.ravel()[:8], rtol=1e-4,
                               atol=1e-5)
    assert lines[-1] == "second_run=1"


def test_c_client_reports_bad_artifact(capi_build, tmp_path):
    _, client = capi_build
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([client, str(tmp_path / "nope"), "4"],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 1
    assert "serving_core.py" in proc.stderr or "create failed" in proc.stderr
