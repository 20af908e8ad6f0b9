"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of exercising distributed paths with local
processes (/root/reference/python/paddle/fluid/tests/unittests/
test_dist_base.py:594) — except on TPU we use XLA's host-platform device
virtualization so multi-chip sharding tests run single-process.

Note: a pytest plug-in or an earlier conftest may have imported jax before
this file runs, and jax reads JAX_PLATFORMS once, at import. So the platform
is set both ways: in os.environ for child processes the tests start, and in
jax.config for this process (it takes effect until the backend starts).
"""
import os
import re

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Hermetic tests: the persistent AOT program cache (default
# <checkout>/.paddle_tpu_cache/aot) must not leak state between test
# runs; "" disables it. Cache tests opt back in with
# explicit tmp dirs via FLAGS_program_cache_dir / Executor kwarg, which
# both take precedence over this env default.
os.environ.setdefault("PADDLE_TPU_PROGRAM_CACHE_DIR", "")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavyweight perf/compile tests excluded from "
        "the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers", "spmd: mesh-native SPMD runtime tests (docs/spmd.md) "
        "— need the 8-device virtual mesh; scripts/run_spmd_tests.sh "
        "runs just these and emits MULTICHIP_r11.json")


def pytest_sessionstart(session):
    n = len(jax.devices())
    assert jax.default_backend() == "cpu", jax.default_backend()
    assert n == 8, f"expected 8 virtual CPU devices, got {n}"
