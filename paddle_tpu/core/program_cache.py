"""Persistent AOT program cache: disk-backed trace + compile reuse.

Kills the retrace+recompile cold start the reference pays per process
(the 12-layer BERT-shaped train step is traced and compiled again in
EVERY interpreter; seconds not measured at HEAD). Two disk layers
share one directory (FLAGS_program_cache_dir, default
<checkout>/.paddle_tpu_cache/aot, env override
PADDLE_TPU_PROGRAM_CACHE_DIR):

  <dir>/trace/<fingerprint>.stablehlo
      jax.export bytes of the fully-lowered Executor step, keyed by
      Program.fingerprint() (op descs/attrs + feed/state signatures +
      lowering-relevant FLAGS + jax/backend versions + a framework
      source token). A hit skips the Python retrace entirely.
  <dir>/policy/<fingerprint>.json
      autotune's winning dispatch forms (paddle_tpu/autotune.py,
      docs/autotune.md) — one JSON entry per (shape-bucket, backend,
      quant-mode) key, version-stamped and self-healing like the
      trace layer, so a tuned deployment restarts straight into its
      winning geometry with zero re-tuning and zero recompiles.
  <dir>/xla/
      jax's persistent compilation cache — XLA binaries keyed by HLO.
      Both the cold and the warm path execute the SAME deserialized
      StableHLO module (the cold path round-trips its own bytes), so
      the warm process's XLA key matches and compilation is skipped
      too: warm start pays neither trace nor compile.

Every entry is written via temp-file + atomic os.replace so concurrent
processes can share one directory; a truncated/corrupt/version-skewed
entry is deleted and falls back to a clean recompile (never a crash,
never wrong fetches — the caller re-exports and overwrites). Counters
land in monitor.py: STAT_program_cache_trace_hit / _trace_miss /
_corrupt / _unexportable / _bytes_read / _bytes_written.

The role model is the reference's serialized-engine flow
(analysis_predictor.cc SaveOptimModel:900 + TRT engine cache), promoted
from a one-off inference artifact into the framework-wide execution
path for both Executor.run and the inference Predictor.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from typing import Optional

MAGIC = b"PTAOT1\n"
FORMAT_VERSION = 1

# set once per process by ensure_xla_cache(); remembered so we re-point
# only a dir WE configured (a user's own jax_compilation_cache_dir
# setting is never overridden)
_xla_cache_dir_set: Optional[str] = None
_framework_token: Optional[str] = None


def _stat_add(name: str, value: float = 1.0) -> None:
    from ..monitor import stat_add
    stat_add(name, value)


class _timed:
    """Record wall time of the enclosed disk operation into a monitor
    latency histogram (always on: these are once-per-program cold
    paths, and their latency is exactly what the hit/miss counters
    can't show — docs/observability.md)."""

    __slots__ = ("name", "_t0")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from ..monitor import timer_observe
        timer_observe(self.name, (time.perf_counter() - self._t0) * 1e6)
        return False


def default_dir() -> str:
    """The auto cache location: env PADDLE_TPU_PROGRAM_CACHE_DIR, else
    the fixed <checkout>/.paddle_tpu_cache/aot — one rule, kept with
    the framework-free serving core."""
    from ..serving_core import default_cache_dir
    return default_cache_dir()


def resolve_dir(override: Optional[str] = None) -> Optional[str]:
    """Effective cache dir or None when disabled. Precedence:
    per-Executor override > FLAGS_program_cache_dir > env > home
    default; "" at any level disables."""
    d = override
    if d is None:
        from ..flags import get_flag
        d = get_flag("FLAGS_program_cache_dir")
    if d is None:
        d = default_dir()
    return d or None


def source_tree_token(root: str) -> str:
    """sha256 over the (relative path, contents) of every .py file under
    `root`. Contents, not mtimes: a fresh copy of the same tree (a new
    checkout, a machine image) must hit what the old copy wrote."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(dirpath, fn)
            with open(p, "rb") as f:
                body = f.read()
            h.update(("%s:%d;" % (os.path.relpath(p, root),
                                  len(body))).encode())
            h.update(body)
    return h.hexdigest()


def framework_token() -> str:
    """source_tree_token of the paddle_tpu package — the op-lowering
    code IS part of the traced computation, so a source change must
    invalidate disk entries. Memoized per process."""
    global _framework_token
    if _framework_token is None:
        _framework_token = source_tree_token(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return _framework_token


def ensure_xla_cache(cache_dir: str) -> None:
    """Point jax's persistent compilation cache at <cache_dir>/xla with
    a zero min-compile-time threshold (small CPU test programs must
    cache too). Never overrides a dir the user configured themselves:
    where JAX_COMPILATION_CACHE_DIR (or jax.config) names a directory,
    that one is used and no other is set here. A failure to turn the
    cache on raises — a cache that silently never engaged reads as a
    slow program, not as an error."""
    global _xla_cache_dir_set
    import jax
    current = jax.config.jax_compilation_cache_dir
    if current and current != _xla_cache_dir_set:
        return  # user-configured; leave it alone
    xla_dir = os.path.join(cache_dir, "xla")
    if current == xla_dir:
        return
    os.makedirs(xla_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", xla_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _xla_cache_dir_set = xla_dir
    # jax latches its cache state at the process's FIRST compile
    # (_initialize_cache runs "at most once"), and the Executor has
    # usually jitted something (PRNG fold-in, state prep) before we
    # get here — un-latch so the next compile picks up the new dir
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()


def _trace_path(cache_dir: str, fingerprint: str) -> str:
    return os.path.join(cache_dir, "trace", fingerprint + ".stablehlo")


def _header_bytes(fingerprint: str) -> bytes:
    import jax
    import jaxlib
    return json.dumps({
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "fingerprint": fingerprint,
    }, sort_keys=True).encode() + b"\n"


def load_trace(cache_dir: str, fingerprint: str) -> Optional[bytes]:
    """Return the serialized jax.export payload for `fingerprint`, or
    None on miss. Any malformed/truncated/version-skewed entry is
    deleted (counted STAT_program_cache_corrupt) so the caller's fresh
    export overwrites it."""
    path = _trace_path(cache_dir, fingerprint)
    try:
        with _timed("TIMER_program_cache_load_us"), \
                open(path, "rb") as f:
            blob = f.read()
    except OSError:
        _stat_add("STAT_program_cache_trace_miss")
        return None
    from ..failpoints import failpoint
    # corrupt/truncate injection lands BEFORE validation: the header +
    # payload checks below must catch the damage and self-heal (discard
    # + fresh export), which is exactly what the chaos tests prove
    blob = failpoint("program_cache.load", blob)
    try:
        if not blob.startswith(MAGIC):
            raise ValueError("bad magic")
        rest = blob[len(MAGIC):]
        nl = rest.index(b"\n")
        hdr = json.loads(rest[:nl])
        payload = rest[nl + 1:]
        import jax
        import jaxlib
        if (hdr.get("format") != FORMAT_VERSION
                or hdr.get("jax") != jax.__version__
                or hdr.get("jaxlib") != jaxlib.__version__
                or hdr.get("fingerprint") != fingerprint
                or not payload):
            raise ValueError("header mismatch")
    except (ValueError, KeyError):
        _stat_add("STAT_program_cache_corrupt")
        _stat_add("STAT_program_cache_trace_miss")
        discard_trace(cache_dir, fingerprint)
        return None
    _stat_add("STAT_program_cache_trace_hit")
    _stat_add("STAT_program_cache_bytes_read", len(blob))
    return payload


def store_trace(cache_dir: str, fingerprint: str, payload: bytes) -> bool:
    """Atomically publish an entry (temp file + os.replace) so a
    concurrent reader sees either nothing or a complete file. IO
    failure disables nothing — it just means no cache this time."""
    path = _trace_path(cache_dir, fingerprint)
    blob = MAGIC + _header_bytes(fingerprint) + payload
    from ..failpoints import failpoint
    blob = failpoint("program_cache.store", blob)
    try:
        with _timed("TIMER_program_cache_store_us"):
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       prefix=".tmp_" + fingerprint[:16])
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
    except OSError:
        return False
    _stat_add("STAT_program_cache_bytes_written", len(blob))
    return True


def discard_trace(cache_dir: str, fingerprint: str) -> None:
    try:
        os.unlink(_trace_path(cache_dir, fingerprint))
    except OSError:
        pass


def has_trace(cache_dir: str, fingerprint: str) -> bool:
    """Cheap existence probe (no counters, no validation) — lets a
    warmup loop report disk-warm vs fresh-compile without paying a
    load. The entry is still fully validated on the real load path."""
    try:
        return os.path.getsize(_trace_path(cache_dir, fingerprint)) > \
            len(MAGIC)
    except OSError:
        return False


# ---------------------------------------------------------------------------
# autotune policy sidecar (paddle_tpu/autotune.py, docs/autotune.md):
# <dir>/policy/<fingerprint>.json holds the winning dispatch form for
# one (shape-bucket, backend, quant-mode) key — same MAGIC + JSON
# header + atomic-replace + corrupt-entry-self-heal recipe as the
# trace layer, so a damaged or version-skewed policy file is deleted
# and the key simply re-tunes (never a crash, never a stale form).
# ---------------------------------------------------------------------------

POLICY_MAGIC = b"PTPOL1\n"
POLICY_FORMAT_VERSION = 1


def _policy_path(cache_dir: str, fingerprint: str) -> str:
    return os.path.join(cache_dir, "policy", fingerprint + ".json")


def policy_fingerprint(meta: dict) -> str:
    """Disk key for one autotune policy entry: sha256 over the
    caller's key metadata (shape-bucket, backend, quant-mode, pins) +
    the lowering flags + jax/jaxlib/backend versions + the framework
    source token — the fn_fingerprint invalidation surface. No knob
    the policy itself chooses is a lowering flag (the kernel form is
    pinned by the engine's `kernel=`, which rides `meta["pins"]`), so
    none has to be kept out of the key."""
    import jax
    import jaxlib
    from ..flags import lowering_snapshot
    h = hashlib.sha256()
    h.update(json.dumps({
        "tag": "autotune_policy",
        "meta": meta,
        "flags": lowering_snapshot(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "framework": framework_token(),
    }, sort_keys=True, default=str).encode())
    return h.hexdigest()


def _policy_header(fingerprint: str) -> bytes:
    import jax
    import jaxlib
    return json.dumps({
        "format": POLICY_FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "fingerprint": fingerprint,
    }, sort_keys=True).encode() + b"\n"


def load_policy(cache_dir: str, fingerprint: str) -> Optional[dict]:
    """Return the persisted policy entry dict for `fingerprint`, or
    None on miss. Malformed / truncated / version-skewed files are
    deleted (STAT_program_cache_corrupt) so the key re-tunes cleanly —
    the same self-heal contract as load_trace."""
    path = _policy_path(cache_dir, fingerprint)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    try:
        if not blob.startswith(POLICY_MAGIC):
            raise ValueError("bad magic")
        rest = blob[len(POLICY_MAGIC):]
        nl = rest.index(b"\n")
        hdr = json.loads(rest[:nl])
        import jax
        import jaxlib
        if (hdr.get("format") != POLICY_FORMAT_VERSION
                or hdr.get("jax") != jax.__version__
                or hdr.get("jaxlib") != jaxlib.__version__
                or hdr.get("fingerprint") != fingerprint):
            raise ValueError("header mismatch")
        entry = json.loads(rest[nl + 1:])
        if not isinstance(entry, dict):
            raise ValueError("payload not a dict")
    except (ValueError, KeyError):
        _stat_add("STAT_program_cache_corrupt")
        discard_policy(cache_dir, fingerprint)
        return None
    return entry


def store_policy(cache_dir: str, fingerprint: str, entry: dict) -> bool:
    """Atomically publish a policy entry (temp file + os.replace).
    IO failure means no persistence this time — never an error."""
    path = _policy_path(cache_dir, fingerprint)
    blob = POLICY_MAGIC + _policy_header(fingerprint) \
        + json.dumps(entry, sort_keys=True, default=str).encode()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=".tmp_" + fingerprint[:16])
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        return False
    return True


def discard_policy(cache_dir: str, fingerprint: str) -> None:
    try:
        os.unlink(_policy_path(cache_dir, fingerprint))
    except OSError:
        pass


def fn_fingerprint(tag: str, meta: dict) -> str:
    """Disk-cache key for a non-Program jitted function (the generation
    engine's prefill/decode steps): sha256 over a caller-provided tag +
    JSON-able metadata (config, shapes, bucket) + lowering-relevant
    FLAGS + jax/backend versions + the framework source token — the
    same invalidation surface as Program.fingerprint, for computations
    that never had a Program."""
    import jax
    import jaxlib
    from ..flags import lowering_snapshot
    h = hashlib.sha256()
    h.update(json.dumps({
        "tag": tag,
        "meta": meta,
        "flags": lowering_snapshot(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "framework": framework_token(),
    }, sort_keys=True, default=str).encode())
    return h.hexdigest()


def exported_entry(cache_dir: str, fingerprint: str, fn, avals,
                   tag: Optional[str] = None, meta: Optional[dict] = None,
                   donate_argnums=()):
    """Generic disk-backed AOT entry: the Executor._aot_entry recipe
    (load -> deserialize -> aval check -> jit(exported.call); on miss
    export, round-trip the bytes, store) for any jit-able `fn` called
    as `fn(*avals)`. Returns the callable, or None when this function
    cannot be disk-cached (unexportable lowering, IO trouble) — the
    caller falls back to plain jax.jit(fn). `donate_argnums` is the
    jitted entry's: the exported module itself aliases nothing, the
    entry around it donates (as Executor._aot_entry does its state).

    With `tag`, the entry is routed through the XLA program accounting
    registry (core/program_accounting.py): compiled at once from the
    avals, cost/memory analysis recorded under the tag, and the
    compiled executable served directly — this is how the generation
    engine's fn_fingerprint entries show up in /programz."""
    import jax
    import jax.export
    ensure_xla_cache(cache_dir)
    exported = None
    payload = load_trace(cache_dir, fingerprint)
    if payload is not None:
        try:
            cand = jax.export.deserialize(payload)
            ours = [(tuple(a.shape), str(a.dtype))
                    for a in jax.tree.leaves(
                        jax.eval_shape(lambda *xs: xs, *avals))]
            theirs = [(tuple(a.shape), str(a.dtype))
                      for a in cand.in_avals]
            if ours == theirs:
                exported = cand
            else:
                raise ValueError("aval mismatch")
        except Exception:
            _stat_add("STAT_program_cache_corrupt")
            discard_trace(cache_dir, fingerprint)
            exported = None
    if exported is None:
        try:
            data = jax.export.export(jax.jit(fn))(*avals).serialize()
            exported = jax.export.deserialize(data)
        except Exception:
            _stat_add("STAT_program_cache_unexportable")
            return None
        store_trace(cache_dir, fingerprint, data)
    # The jitted entry is NAMED by tag and fingerprint, for two reasons.
    # jax's persistent compile cache leaves metadata out of its key, so
    # an executable compiled before a change of jax.named_scope names
    # alone would come back WITHOUT the new names, and the device
    # trace's readers would read nothing; the module's name is part of
    # that key, and the fingerprint holds the framework's source token,
    # so the two are told apart. And the trace's `XLA Modules` line then
    # shows `jit_generation_mixed_<fingerprint>`, not `jit_call`.
    def entry_fn(*args):
        return exported.call(*args)
    from . import program_accounting
    entry_fn.__name__ = "%s_%s" % (
        program_accounting.safe_tag(tag or "exported"), fingerprint[:12])
    entry = jax.jit(entry_fn, donate_argnums=donate_argnums)
    if tag is not None:
        entry = program_accounting.accounted(
            entry, avals, tag=program_accounting.safe_tag(tag),
            key=fingerprint[:12], meta=meta)
    return entry


def warmup_ladder(buckets, compile_one) -> dict:
    """Compile-ahead of a shape-bucket ladder (docs/serving.md): run
    `compile_one(bucket)` for every bucket size, ascending, and report
    per-bucket wall time plus whether the trace came from disk —
    the serving analog of the reference pre-building one TRT engine
    per optimization profile. Counters: STAT_program_cache_warm per
    bucket compiled; failures are recorded, not raised (a bucket the
    program cannot trace at must not take the whole ladder down)."""
    from ..monitor import stat_get
    report = {}
    for b in sorted(set(int(x) for x in buckets)):
        h0 = stat_get("STAT_program_cache_trace_hit")
        t0 = time.perf_counter()
        try:
            compile_one(b)
        except Exception as e:
            report[b] = {"error": repr(e)[:200]}
            continue
        _stat_add("STAT_program_cache_warm")
        report[b] = {
            "seconds": round(time.perf_counter() - t0, 4),
            "disk_warm":
                stat_get("STAT_program_cache_trace_hit") > h0,
        }
    return report
