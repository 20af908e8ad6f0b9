"""Top-level API surface checker (reference-parity guard).

Parses every NON-commented `from .<mod> import <name>` line of the
reference's python/paddle/__init__.py and asserts the same name resolves
on paddle_tpu's top level. Mirrors the role of the reference's own
API-spec diffing (tools/check_api_compatible.py): the public surface
may only shrink deliberately, with the absence documented below.

Exit 0 = parity holds. Run by tests/test_op_registry_compat.py.
"""
import os
import re
import sys

REF_INIT = "/root/reference/python/paddle/__init__.py"

# Documented intentional absences (each with the reason):
ALLOWED_ABSENT = {
    # CUDA-only plumbing with no TPU meaning; the porting analogs exist
    # (CUDAPlace/TPUPlace alias, get_cudnn_version() -> None).
    "CUDAPinnedPlace",
    # `import paddle.nn.functional as F`-style subpackage re-exports the
    # reference lists via `from . import nn` equivalents we also have;
    # only bare-module names appear here.
}


REF_ROOT = "/root/reference/python/paddle"

# second-level namespaces diffed the same way (module path -> attr path)
SUB_NAMESPACES = [
    "nn", "nn/functional", "optimizer", "metric", "static", "io",
    "distributed", "tensor", "fluid", "incubate",
]

# fluid members that are deliberately absent (documented design
# discharge; everything else must resolve)
FLUID_ALLOWED_ABSENT = {
    # pybind/C++ internals with no python-facing role here: the C++
    # core IS jax/XLA (fluid/core.py keeps the names ported code uses)
    "core_avx", "core_noavx", "libpaddle",
    # py2 compat module (reference imports `sys` etc. — filtered by
    # regex already)
}


def _ref_names(path):
    """All top-level names a reference __init__ binds via from-imports
    (EVERY name on multi-name lines, including backslash
    continuations) and `import paddle.x` statements."""
    names = set()
    text = open(path).read().replace("\\\n", " ")
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("#") or "__future__" in line:
            continue
        m = re.match(r"from [.\w]+ import (.+)", line)
        if m:
            frag = m.group(1).split("#")[0]
            for item in frag.split(","):
                item = item.strip().strip("()")
                if " as " in item:
                    item = item.split(" as ")[1].strip()
                if re.fullmatch(r"\w+", item) and not \
                        item.startswith("_"):
                    names.add(item)
        m = re.match(r"import paddle\.(\w+)", line)
        if m:
            names.add(m.group(1))
    return names


def main() -> int:
    if not os.path.exists(REF_INIT):
        print("reference __init__.py not found; skipping")
        return 0
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import paddle_tpu as pt

    rc = 0
    names = _ref_names(REF_INIT)
    missing = sorted(n for n in names
                     if not hasattr(pt, n) and n not in ALLOWED_ABSENT)
    print("top-level: %d reference names, %d missing"
          % (len(names), len(missing)))
    if missing:
        print("MISSING top-level:", missing)
        rc = 1

    for sub in SUB_NAMESPACES:
        path = os.path.join(REF_ROOT, sub, "__init__.py")
        if not os.path.exists(path):
            continue
        mod = pt
        for part in sub.split("/"):
            mod = getattr(mod, part)
        sub_names = _ref_names(path)
        allowed = FLUID_ALLOWED_ABSENT if sub == "fluid" else set()
        sub_missing = sorted(n for n in sub_names
                             if not hasattr(mod, n) and n not in allowed)
        print("%-14s %d reference names, %d missing"
              % (sub.replace("/", "."), len(sub_names),
                 len(sub_missing)))
        if sub_missing:
            print("MISSING %s:" % sub, sub_missing)
            rc = 1

    stale = sorted(n for n in ALLOWED_ABSENT if hasattr(pt, n))
    if stale:
        print("NOTE: ALLOWED_ABSENT entries now present (prune):", stale)
    return rc


if __name__ == "__main__":
    sys.exit(main())
