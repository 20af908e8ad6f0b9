"""Standalone serving core for export_serialized() artifacts.

Deliberately free of any paddle_tpu package dependency (imports: json,
os, numpy, jax) so non-Python hosts can load it without pulling the
framework in: `export_serialized` copies this file INTO the artifact
directory, and the inference C API (csrc/capi.cc) embeds a CPython
interpreter and loads `<artifact>/serving_core.py` by path — the
TPU-native analog of the reference shipping a self-contained serialized
engine behind its C API
(/root/reference/paddle/fluid/inference/capi/c_api.cc:1,
analysis_predictor.cc SaveOptimModel:900).
"""
from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["SerializedCore"]

# order IS the C ABI dtype enum (csrc/pt_c_api.h) — append only
_DTYPES = ["float32", "int32", "int64", "float64", "uint8",
           "float16", "bfloat16", "bool"]

# shape-bucket ladder for variable-batch serving (env because this file
# ships framework-free inside the artifact; same spec grammar as
# FLAGS_predictor_shape_buckets, "" disables)
_BUCKET_ENV = "PADDLE_TPU_SHAPE_BUCKETS"

# mesh for single-host SPMD serving (same spec grammar as
# paddle_tpu.mesh.MeshSpec — "dp4", "dp=4,mp=2", "dp4xmp2"; unset/""
# serves single-device). The exported StableHLO is single-logical-
# device; jit re-partitions it across the mesh from the feeds' input
# shardings (batch dim sharded over the data axis), so one artifact
# serves both layouts.
_MESH_ENV = "PADDLE_TPU_MESH"


def _mesh_from_env():
    """Parse PADDLE_TPU_MESH into (jax Mesh, data_axis) over the first
    prod(sizes) local devices, or (None, None) when unset. Framework-
    free twin of paddle_tpu.mesh.MeshSpec: axes split on 'x'/',' with
    each axis 'name<size>', 'name=<size>' or 'name:<size>'."""
    s = os.environ.get(_MESH_ENV, "").strip()
    if not s:
        return None, None
    import re
    import jax
    from jax.sharding import Mesh
    axes = []
    for part in re.split(r"[x,]", s):
        part = part.strip()
        if not part:
            continue
        m = re.match(r"^([A-Za-z_][A-Za-z_0-9]*?)[=:]?([0-9]+)$", part)
        if m is None:
            raise ValueError("bad %s axis %r (want e.g. dp4 or dp=4)"
                             % (_MESH_ENV, part))
        axes.append((m.group(1), int(m.group(2))))
    if not axes:
        return None, None
    n = 1
    for _, k in axes:
        n *= k
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            "%s=%r needs %d devices but only %d are visible — on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=%d"
            % (_MESH_ENV, s, n, len(devs), n))
    grid = np.array(devs[:n]).reshape([k for _, k in axes])
    mesh = Mesh(grid, tuple(name for name, _ in axes))
    data_axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
    return mesh, data_axis


def _bucket_ladder():
    s = os.environ.get(_BUCKET_ENV, "pow2:128").strip()
    if not s:
        return []
    if s.startswith("pow2:"):
        cap, ladder, b = int(s[len("pow2:"):]), [], 1
        while b <= cap:
            ladder.append(b)
            b *= 2
        return ladder
    return sorted({int(x) for x in s.split(",") if x.strip()} - {0})


def _np_dtype(code: int):
    name = _DTYPES[code]
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def default_cache_dir() -> str:
    """The shared AOT cache dir: env PADDLE_TPU_PROGRAM_CACHE_DIR (empty
    string disables), else ONE fixed, git-ignored directory inside the
    checkout that holds this file's package. Fixed because the path is
    part of jax's compilation-cache key: a directory named after a pid,
    a time or a temporary name never hits. Not the home directory: a
    fresh machine has none worth finding, and a checkout must not write
    around itself. Lives here, not in core/program_cache.py (which
    calls it), because this file is framework-free and ships inside the
    artifact."""
    d = os.environ.get("PADDLE_TPU_PROGRAM_CACHE_DIR")
    if d is None:
        d = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".paddle_tpu_cache", "aot")
    return d


def _maybe_enable_compile_cache():
    """Point jax's persistent compilation cache at default_cache_dir()/xla so a
    serving process restart skips the XLA binary compile of the
    deserialized StableHLO. Where JAX_COMPILATION_CACHE_DIR (or
    jax.config) already names a directory that one is used and none is
    set here."""
    d = default_cache_dir()
    if not d:
        return
    import jax
    if jax.config.jax_compilation_cache_dir:
        return  # respect an explicit user setting
    xla_dir = os.path.join(d, "xla")
    os.makedirs(xla_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", xla_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches cache state at the first compile of the process;
    # un-latch so the new dir takes effect even if something jitted
    # before this call
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()


class SerializedCore:
    """Load + run a serialized artifact (StableHLO + params + signature).

    run() takes/returns plain numpy arrays; dtype_code()/shape helpers
    exist for flat-ABI callers (the C API) that speak in enums.
    """

    def __init__(self, path: str):
        _maybe_enable_compile_cache()
        import jax
        import jax.export
        with open(os.path.join(path, "model.stablehlo"), "rb") as f:
            self._exported = jax.export.deserialize(f.read())
        with open(os.path.join(path, "signature.json")) as f:
            sig = json.load(f)
        self.feed_names = list(sig["feed_names"])
        self.fetch_names = list(sig["fetch_names"])
        loaded = np.load(os.path.join(path, "params.npz"))
        self._state = {k: loaded[k] for k in loaded.files}
        # PADDLE_TPU_MESH: replicate params over the mesh once at load;
        # run() stages each batch sharded and jit partitions the module
        self._mesh, self._data_axis = _mesh_from_env()
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self._mesh, PartitionSpec())
            self._state = {k: jax.device_put(v, rep)
                           for k, v in self._state.items()}
        # jit once: repeated run() hits the compiled executable instead
        # of re-staging the exported call, and the compile itself lands
        # in (or comes from) the persistent cache enabled above
        self._call = jax.jit(self._exported.call)
        self._batch_spec = self._recover_batch_spec()
        # visible serving behavior for callers with no metrics registry
        self.stats = {"calls": 0, "padded_calls": 0, "pad_rows": 0,
                      "mesh_devices": int(self._mesh.size)
                      if self._mesh is not None else 0}

    def _recover_batch_spec(self):
        """The artifact's recorded leading dim per feed: an int for a
        static export (smaller batches pad UP to it — one compiled
        program serves any b <= B), the string "dyn" for a symbolic
        dynamic_batch export (batches pad to the env bucket ladder so
        steady traffic hits a few warm XLA specializations), or None
        when the export structure can't be recovered (no padding)."""
        try:
            import jax
            args, _kw = jax.tree.unflatten(self._exported.in_tree,
                                           list(self._exported.in_avals))
            spec = {}
            for n, av in args[1].items():
                if not len(av.shape):
                    continue
                d = av.shape[0]
                spec[n] = int(d) if isinstance(d, int) else "dyn"
            return spec or None
        except Exception:
            return None

    def _pad_plan(self, feed_map):
        """(padded_feed_map, true_rows, target) — true_rows is None
        when no row padding happened (outputs returned as-is); target
        is the padded batch (only outputs with that leading dim are
        sliced back, so non-batch outputs pass through untouched)."""
        if not self._batch_spec:
            return feed_map, None, None
        dims = {v.shape[0] for v in feed_map.values() if v.ndim}
        if len(dims) != 1:
            return feed_map, None, None
        b = dims.pop()
        kinds = set(self._batch_spec.values())
        if kinds == {"dyn"}:
            ladder = _bucket_ladder()
            target = next((t for t in ladder if t >= b), None)
            if target is None or target == b:
                return feed_map, None, None
        elif "dyn" not in kinds and len(kinds) == 1:
            target = kinds.pop()
            if b == target:
                return feed_map, None, None
            if b > target:
                raise ValueError(
                    "batch %d exceeds the artifact's compiled batch %d "
                    "(re-export with a larger example batch or "
                    "dynamic_batch=True)" % (b, target))
        else:
            return feed_map, None, None
        padded = {}
        for n, v in feed_map.items():
            if v.ndim:
                padded[n] = np.pad(v, [(0, target - v.shape[0])]
                                   + [(0, 0)] * (v.ndim - 1))
            else:
                padded[n] = v
        self.stats["padded_calls"] += 1
        self.stats["pad_rows"] += target - b
        return padded, b, target

    def run(self, feeds):
        if len(feeds) != len(self.feed_names):
            raise ValueError("expected %d feeds (%s), got %d"
                             % (len(self.feed_names), self.feed_names,
                                len(feeds)))
        feed_map = {n: np.asarray(v)
                    for n, v in zip(self.feed_names, feeds)}
        feed_map, true_rows, target = self._pad_plan(feed_map)
        if self._mesh is not None:
            feed_map = self._place_mesh(feed_map)
        self.stats["calls"] += 1
        outs = self._call(self._state, feed_map)
        host = [np.ascontiguousarray(np.asarray(o)) for o in outs]
        if true_rows is not None:
            host = [o[:true_rows] if o.ndim and
                    o.shape[0] == target else o for o in host]
        return host

    def _place_mesh(self, feed_map):
        """PADDLE_TPU_MESH serving: stage feeds over the mesh — batch
        dim sharded over the data axis when it divides evenly, else
        replicated — so jit partitions the deserialized module across
        the local devices (single-host SPMD, no framework import)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = self._mesh.shape[self._data_axis]
        placed = {}
        for k, v in feed_map.items():
            if v.ndim and n > 1 and v.shape[0] % n == 0:
                spec = P(self._data_axis, *([None] * (v.ndim - 1)))
            else:
                spec = P()
            placed[k] = jax.device_put(v, NamedSharding(self._mesh, spec))
        return placed

    def warmup_buckets(self, example_feeds, max_bucket=None):
        """Compile-ahead: run one zero-filled batch per serving shape so
        the first real request of any bucketed size hits a warm XLA
        executable (the compiles land in the persistent cache enabled at
        load). The counterpart of Predictor.warmup_buckets with the same
        report shape ({bucket: {"seconds"} | {"error"}}), which is what
        lets serving.PredictorPool.warmup — and the front door's
        hot-swap warmup (frontdoor.py) — treat a SerializedCore like a
        Predictor. For a dynamic_batch export the targets are the env
        bucket ladder (PADDLE_TPU_SHAPE_BUCKETS, capped by
        `max_bucket`); for a static export the single compiled batch is
        warmed. Numpy-only on purpose — this file ships inside the
        artifact."""
        if len(example_feeds) != len(self.feed_names):
            raise ValueError("expected %d example feeds (%s), got %d"
                             % (len(self.feed_names), self.feed_names,
                                len(example_feeds)))
        examples = [np.asarray(v) for v in example_feeds]
        kinds = set((self._batch_spec or {}).values())
        if kinds == {"dyn"}:
            targets = _bucket_ladder()
            if max_bucket is not None:
                targets = [b for b in targets if b <= max_bucket] \
                    or targets[:1]
        elif kinds and "dyn" not in kinds and len(kinds) == 1:
            targets = [kinds.pop()]
        else:
            targets = [max(1, next((v.shape[0] for v in examples
                                    if v.ndim), 1))]
        import time as _time
        report = {}
        for bkt in targets:
            feeds = [np.zeros((bkt,) + v.shape[1:], v.dtype)
                     if v.ndim else v for v in examples]
            t0 = _time.monotonic()
            try:
                self.run(feeds)
                report[bkt] = {"seconds":
                               round(_time.monotonic() - t0, 4)}
            except Exception as e:  # partial warmup stays usable
                report[bkt] = {"error": repr(e)}
        return report

    # --- flat-ABI helpers for the C API --------------------------------
    @staticmethod
    def dtype_code(arr) -> int:
        return _DTYPES.index(str(arr.dtype))

    @staticmethod
    def from_flat(buf: bytes, dtype_code: int, shape) -> np.ndarray:
        return np.frombuffer(buf, dtype=_np_dtype(dtype_code)).reshape(
            [int(s) for s in shape]).copy()
