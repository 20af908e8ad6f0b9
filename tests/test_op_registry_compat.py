"""API-compat guard (SURVEY §4.6 — the reference's check_op_desc.py
golden-spec diffing): the live registry must not silently drop ops or
change signatures vs tools/op_registry_golden.json."""
import json
import os
import subprocess
import sys


def test_registry_matches_golden():
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_op_registry.py")
    proc = subprocess.run([sys.executable, tools], capture_output=True,
                          text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_golden_has_full_surface():
    golden = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "op_registry_golden.json")
    ops = json.load(open(golden))
    assert len(ops) >= 476
    # spot-check signature capture of a mutating optimizer op
    assert ops["sgd"]["inplace_map"].get("ParamOut") == "Param"
    assert ops["lookup_table_v2"]["non_diff_inputs"] == ["Ids"]


def test_api_surface_matches_reference():
    """Top-level name parity with the reference's python/paddle
    __init__ (tools/check_api_surface.py; reference analog:
    tools/check_api_compatible.py)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_api_surface.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, tool], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_tpu_scripts_parse():
    """The scripts/ are TPU-only (never executed under pytest); at
    least guarantee they stay syntactically valid (.py via ast, .sh via
    bash -n)."""
    import ast
    import shutil
    import subprocess
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    checked = 0
    for fn in sorted(os.listdir(root)):
        path = os.path.join(root, fn)
        if fn.endswith(".py"):
            ast.parse(open(path).read(), filename=fn)
            checked += 1
        elif fn.endswith(".sh") and shutil.which("bash"):
            subprocess.run(["bash", "-n", path], check=True,
                           capture_output=True)
            checked += 1
    assert checked >= 3


def test_tpu_scripts_import():
    """ast.parse once let broken scripts through: invoked as
    `python scripts/x.py` the repo root was NOT on sys.path, and one
    used the nonexistent np.bfloat16 — both only show when the file
    runs. Actually EXECUTE the scripts' import + setup surface on CPU,
    from a cwd that is not the repo root, as a user launches them."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # pinned to the CPU through the child's environment
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # scripts must self-insert the repo root

    # tpu_experiments --selftest runs imports + tiny-shape jits, rc=0
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "tpu_experiments.py"),
         "--selftest"], capture_output=True, text=True, timeout=300,
        cwd="/tmp", env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "selftest OK" in proc.stdout

    # the TPU-asserting scripts must die on the backend check (meaning
    # all their imports resolved), not on any import failure. NB: a bare
    # `'tpu' in err` would match 'paddle_tpu' inside any traceback — the
    # checks must pin the actual backend-assert message.
    for script in ("inkernel_parity.py", "profile_bert.py",
                   "profile_resnet.py"):
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "scripts", script)],
            capture_output=True, text=True, timeout=300, cwd="/tmp",
            env=env)
        assert proc.returncode != 0
        err = proc.stdout + proc.stderr
        assert "ModuleNotFoundError" not in err, (script, err)
        assert "ImportError" not in err, (script, err)
        assert ("AssertionError: cpu" in err
                or "real TPU backend" in err), (script, err)
