"""Serving-side quantization: checkpoint conversion + quantized math.

Connects the contrib/slim QAT machinery to the serving hot path
(ISSUE 15 / ROADMAP open item 1). One shared scale contract ties the
two worlds together:

    scale == per-channel fp32 ABSMAX (the clipping range), laid out
    [n_channels] along the quant axis (scalar scales keep shape [1]).
    quantize:   q = round(x * GRID / scale)  clipped to the int grid
    dequantize: x ~= q * scale / GRID

This is exactly what contrib/slim's freeze pass stores in
`<name>.quant_scale` and what ops/quantize.py's
fake_channel_wise_dequantize_max_abs consumes (Out = X*Scale/bins), so
QAT-exported scales round-trip losslessly — the absmax itself is
stored, never a pre-divided reciprocal that would lose a ulp on the
way back.

Flat generation checkpoints (generation/model.py param dicts) carry the
quantized weight under the original key and the scale under
`<name>::scale` (SCALE_SUFFIX); program/scope checkpoints (inference
Predictor) keep slim's `<name>.quant_scale` naming. `from_qat` adapts
the latter to the former.

GRID is 127 for int8 (symmetric, -127..127 — the slim convention for
8-bit: (1 << (bits-1)) - 1) and 448 for fp8-e4m3 (the format's max
normal). fp8 is weight-only storage: values are scaled into the e4m3
range, stored as fp8, and upcast for the matmul — supported only where
the jax build ships float8_e4m3fn (supports_fp8()).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

__all__ = [
    "GRID_INT8", "GRID_FP8", "SCALE_SUFFIX", "MODES", "KV_DTYPES",
    "supports_fp8", "grid_for_mode", "grid_for_dtype", "storage_dtype",
    "resolve_wire_mode",
    "channel_absmax", "quantize_array", "dequantize_array",
    "matmul", "embed", "qmatmul", "quantize_kv_rows",
    "quantize_decoder_params", "is_quantized", "weight_bytes_saved",
    "from_qat", "to_qat",
    "save_quantized", "load_quantized",
    "quantize_program_weights",
]

# symmetric int8 grid: (1 << (8-1)) - 1, matching contrib/slim wbins
GRID_INT8 = 127.0
# fp8-e4m3 max normal — values are scaled so absmax lands on it
GRID_FP8 = 448.0
# scale key suffix in FLAT param dicts (generation checkpoints).
# "::" cannot collide with program var names (slim uses ".quant_scale")
SCALE_SUFFIX = "::scale"
MODES = ("off", "int8", "fp8")
# "bf16" is a plain narrower pool (no scale pools, no grid); "int8" and
# "fp8" store a payload on a grid beside per-token-per-head scales
KV_DTYPES = ("fp32", "bf16", "int8", "fp8")


def supports_fp8() -> bool:
    """fp8-e4m3 capability probe: the dtype must exist in this jax
    build AND round-trip a conversion on the current backend."""
    import jax.numpy as jnp
    if not hasattr(jnp, "float8_e4m3fn"):
        return False
    try:
        x = jnp.asarray([1.0, -2.5], jnp.float32)
        y = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return bool(np.allclose(np.asarray(y), np.asarray(x)))
    except Exception:
        return False


def grid_for_mode(mode: str) -> float:
    if mode == "int8":
        return GRID_INT8
    if mode == "fp8":
        return GRID_FP8
    raise ValueError("unknown quant mode %r (expected int8|fp8)" % mode)


def grid_for_dtype(dtype) -> float:
    """Grid for a stored array's dtype — lets consumers (the paged
    attention kernels) derive the dequant constant from the pool
    itself instead of threading the mode string around."""
    import jax.numpy as jnp
    if dtype == jnp.int8:
        return GRID_INT8
    if hasattr(jnp, "float8_e4m3fn") and dtype == jnp.float8_e4m3fn:
        return GRID_FP8
    raise ValueError("no quant grid for dtype %r" % (dtype,))


def storage_dtype(mode: str):
    import jax.numpy as jnp
    if mode == "int8":
        return jnp.int8
    if mode == "fp8":
        if not supports_fp8():
            raise RuntimeError(
                "quant mode 'fp8' requires a jax build with "
                "float8_e4m3fn on this backend (supports_fp8() is "
                "False) — use 'int8'")
        return jnp.float8_e4m3fn
    raise ValueError("unknown quant mode %r" % mode)


_WIRE_WARNED = False


def resolve_wire_mode(mode: str, *, warn: bool = True) -> str:
    """Resolve a requested collective wire mode against the backend.

    Unlike :func:`storage_dtype` (which RAISES for fp8 without backend
    support — a checkpoint stored in a dtype the build lacks is
    unrecoverable), a collective wire is negotiable: "fp8" degrades to
    the int8 wire with a one-time warning when :func:`supports_fp8` is
    false, because the exchange still has to happen. "fp32"/"int8"
    pass through; anything else raises. mesh/collectives.py resolves
    once at plan time so the traced program and the byte census agree
    on the dtype actually on the wire."""
    if mode in ("fp32", "int8"):
        return mode
    if mode == "fp8":
        if supports_fp8():
            return "fp8"
        global _WIRE_WARNED
        if warn and not _WIRE_WARNED:
            _WIRE_WARNED = True
            import warnings
            warnings.warn(
                "collective wire mode 'fp8' needs float8_e4m3fn "
                "(quant.supports_fp8() is False on this backend) — "
                "falling back to the int8 wire", stacklevel=2)
        return "int8"
    raise ValueError(
        "unknown collective wire mode %r (expected fp32|int8|fp8)"
        % (mode,))


def channel_absmax(w: np.ndarray, axis: int) -> np.ndarray:
    """Per-channel absmax along `axis`, zero-guarded (an all-zero
    channel gets scale 1.0 so it quantizes AND dequantizes to exact
    zeros). The load-bearing property, shared with contrib/slim's
    freeze pass: the STORED scale always equals the divisor actually
    used, so export -> load round-trips losslessly."""
    w = np.asarray(w, np.float32)
    red = tuple(i for i in range(w.ndim) if i != axis)
    s = np.abs(w).max(axis=red) if red else np.abs(w)
    s = s.reshape(-1) if s.ndim else s.reshape(1)
    return np.where(s <= 0.0, 1.0, s).astype(np.float32)


def _bshape(w: np.ndarray, axis: int) -> Tuple[int, ...]:
    return tuple(w.shape[axis] if i == axis else 1
                 for i in range(w.ndim))


def quantize_array(w, axis: int, mode: str):
    """fp32 array -> (stored, scale): per-channel symmetric quant along
    `axis` under the shared absmax contract. int8 rounds+clips onto the
    integer grid; fp8 scales absmax onto 448 and casts."""
    import jax.numpy as jnp
    w = np.asarray(w, np.float32)
    s = channel_absmax(w, axis)
    sb = s.reshape(_bshape(w, axis))
    grid = grid_for_mode(mode)
    scaled = w / sb * grid
    if mode == "int8":
        q = np.clip(np.round(scaled), -GRID_INT8, GRID_INT8)
        stored = jnp.asarray(q.astype(np.int8))
    else:
        stored = jnp.asarray(scaled).astype(storage_dtype(mode))
    return stored, jnp.asarray(s)


def dequantize_array(q, scale, axis: int):
    """Inverse of quantize_array: q * scale / grid along `axis`."""
    import jax.numpy as jnp
    grid = grid_for_dtype(q.dtype)
    sb = jnp.reshape(scale, tuple(q.shape[i] if i == axis else 1
                                  for i in range(q.ndim)))
    return q.astype(jnp.float32) * (sb * (1.0 / grid))


def qmatmul(x, wq, scale):
    """int8 x int8 -> int32 -> scale matmul. `x` fp32 [..., K], `wq`
    int8 [K, N], `scale` fp32 absmax [N] or [1]. Activations are
    dynamically quantized per-row (absmax over the contraction axis) so
    the inner product runs on the integer units; the int32 accumulator
    is rescaled by (row_absmax/127) * (w_absmax/127)."""
    import jax
    import jax.numpy as jnp
    ax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    xs = jnp.where(ax > 0, ax * (1.0 / GRID_INT8), 1.0)
    xq = jnp.clip(jnp.round(x / xs), -GRID_INT8, GRID_INT8) \
        .astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * (scale * (1.0 / GRID_INT8))


def matmul(params: Dict, name: str, x):
    """`x @ params[name]` with the quantized path keyed off the
    presence of `<name>::scale` — absent scale takes the EXACT fp32
    expression, so serving with quant off stays bitwise-identical."""
    import jax.numpy as jnp
    w = params[name]
    sc = params.get(name + SCALE_SUFFIX)
    if sc is None:
        return x @ w
    if w.dtype == jnp.int8:
        return qmatmul(x, w, sc)
    # fp8 (or any float storage): weight-only — dequant then fp32 dot
    grid = grid_for_dtype(w.dtype)
    return x @ (w.astype(jnp.float32) * (sc * (1.0 / grid)))


def embed(params: Dict, name: str, idx):
    """Embedding gather with per-row dequant (quant axis 0): gather the
    stored rows AND their scales, multiply after the gather so only the
    touched rows dequantize."""
    import jax.numpy as jnp
    e = params[name][idx]
    sc = params.get(name + SCALE_SUFFIX)
    if sc is None:
        return e
    grid = grid_for_dtype(params[name].dtype)
    return e.astype(jnp.float32) * (sc[idx] * (1.0 / grid))[..., None]


def quantize_kv_rows(x, store_dtype):
    """Quantize freshly-computed K or V rows for the paged pool:
    `x` fp32 [..., H, D] -> (stored [..., H, D] int8/fp8,
    scales [..., H] fp32 absmax over D). Per-TOKEN-per-head scales are
    the pool granularity (vs per-block) because blocks fill
    incrementally: a new position's write must never retro-scale
    positions already in the block (prefix-cache shared blocks are
    immutable once published)."""
    import jax.numpy as jnp
    grid = grid_for_dtype(store_dtype)
    s = jnp.max(jnp.abs(x), axis=-1)
    s = jnp.where(s > 0, s, 1.0)
    scaled = x * (grid / s)[..., None]
    if store_dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -grid, grid).astype(store_dtype)
    else:
        q = scaled.astype(store_dtype)
    return q, s


def _decoder_axes(params: Dict) -> Dict[str, int]:
    """Quant axis per quantizable decoder param: embeddings per-row
    (axis 0 — dequant after gather), matmul weights per-OUTPUT-channel
    (axis 1 — slim's _weight_quant_axis for mul/matmul). 1-D params
    (LN gains/biases, mlp biases) stay fp32."""
    axes = {}
    for name, w in params.items():
        if name.endswith(SCALE_SUFFIX) or getattr(w, "ndim", 0) < 2:
            continue
        axes[name] = 0 if name.endswith(("tok_emb", "pos_emb")) else 1
    return axes


def is_quantized(params: Dict) -> bool:
    return any(k.endswith(SCALE_SUFFIX) for k in params)


def quantize_decoder_params(params: Dict, mode: str) -> Dict:
    """Post-training conversion of a flat fp32 decoder checkpoint
    (generation/model.py init_params layout): every >=2-D weight
    becomes `name` (int8/fp8) + `name::scale` (fp32 absmax); 1-D
    params pass through untouched. Idempotent on already-quantized
    checkpoints."""
    if mode == "off":
        return dict(params)
    if mode not in MODES:
        raise ValueError("unknown quant mode %r (one of %s)"
                         % (mode, (MODES,)))
    if is_quantized(params):
        return dict(params)
    out: Dict = {}
    axes = _decoder_axes(params)
    for name, w in params.items():
        if name in axes:
            q, s = quantize_array(np.asarray(w), axes[name], mode)
            out[name] = q
            out[name + SCALE_SUFFIX] = s
        else:
            out[name] = w
    return out


def weight_bytes_saved(params: Dict) -> int:
    """fp32 bytes minus actual stored bytes across quantized weights
    (scale storage counted against the saving) — the value behind
    GAUGE_quant_weight_bytes_saved."""
    saved = 0
    for name, w in params.items():
        if name.endswith(SCALE_SUFFIX):
            saved -= int(np.prod(w.shape)) * 4
            continue
        if (name + SCALE_SUFFIX) in params:
            n = int(np.prod(w.shape))
            saved += n * 4 - n * np.dtype(
                np.int8 if str(w.dtype) == "int8" else np.uint8).itemsize
    return int(saved)


def from_qat(weights: Dict, mode: str = "int8") -> Dict:
    """Adapt a slim-exported dict ({name: int-grid weight,
    name + '.quant_scale': absmax} — the freeze/ConvertToInt8 output)
    to the flat serving layout. Scales are carried over VERBATIM
    (same fp32 absmax contract), so export -> load is lossless."""
    import jax.numpy as jnp
    out: Dict = {}
    for name, w in weights.items():
        if name.endswith(".quant_scale"):
            continue
        s = weights.get(name + ".quant_scale")
        if s is None:
            out[name] = w
            continue
        q = np.clip(np.asarray(w, np.float32), -GRID_INT8, GRID_INT8)
        out[name] = jnp.asarray(q.astype(np.int8))
        out[name + SCALE_SUFFIX] = jnp.asarray(
            np.asarray(s, np.float32).reshape(-1))
    return out


def to_qat(params: Dict) -> Dict:
    """Inverse adapter (serving layout -> slim's .quant_scale naming),
    for exporting a converted checkpoint back through slim tooling."""
    out: Dict = {}
    for name, w in params.items():
        if name.endswith(SCALE_SUFFIX):
            out[name[:-len(SCALE_SUFFIX)] + ".quant_scale"] = w
        else:
            out[name] = w
    return out


def save_quantized(path: str, params: Dict, mode: str) -> None:
    """npz serving artifact: arrays verbatim + the quant mode under the
    reserved key `__quant_mode__`."""
    arrays = {k: np.asarray(v) for k, v in params.items()}
    arrays["__quant_mode__"] = np.asarray(mode)
    np.savez(path, **arrays)


def load_quantized(path: str) -> Tuple[Dict, str]:
    """Load a save_quantized() artifact -> (params, mode). int8 weights
    come back int8; scales fp32."""
    import jax.numpy as jnp
    data = np.load(path, allow_pickle=False)
    mode = "off"
    params: Dict = {}
    for k in data.files:
        if k == "__quant_mode__":
            mode = str(data[k])
            continue
        params[k] = jnp.asarray(data[k])
    return params, mode


# --- program/scope integration (inference.Predictor) -------------------

def quantize_program_weights(program, scope, mode: str = "int8",
                             scale_suffix: str = ".quant_scale") -> int:
    """Weight-only quantization of a loaded inference Program: every
    persistable >=2-D fp32 weight feeding a matmul-family op is stored
    int8 (+ `<name>.quant_scale` absmax var) and a
    fake_channel_wise_dequantize_max_abs op is inserted so consumers
    see the dequantized weight — XLA fuses the convert+scale into the
    matmul, while scope memory holds int8. Returns fp32 bytes saved.

    Reuses slim's op vocabulary end to end, so a program frozen by the
    QAT passes and a program converted here are the same dialect (and
    export_serialized works unchanged — the dequant traces into the
    StableHLO artifact for SerializedCore)."""
    if mode == "off":
        return 0
    if mode == "fp8":
        # the program dialect stores int8; fp8 stays a flat-checkpoint
        # (generation) capability until the scope grows an fp8 tensor
        raise ValueError(
            "quantize_program_weights supports mode='int8' (fp8 is "
            "flat-checkpoint only)")
    return _quantize_program_int8(program, scope, scale_suffix)


def _quantize_program_int8(program, scope, scale_suffix: str) -> int:
    from ..core.program import OpDesc
    matmul_ops = ("mul", "matmul", "matmul_v2")
    saved = 0
    for block in program.blocks:
        new_ops = []
        converted = {}  # weight name -> dequantized var name
        for op in block.ops:
            for slot in list(op.inputs):
                names = op.input(slot)
                if not names:
                    continue
                rewritten = list(names)
                for i, n in enumerate(names):
                    if op.type in matmul_ops and slot in ("Y", "W"):
                        dq = converted.get(n)
                        if dq is None:
                            dq = _convert_weight(block, scope, new_ops,
                                                 op, n, scale_suffix)
                            if dq is not None:
                                converted[n] = dq
                                w = np.asarray(scope.find_var(n))
                                saved += int(w.size) * 3
                        if dq is not None:
                            rewritten[i] = dq
                op.inputs[slot] = rewritten
            new_ops.append(op)
        block.ops = new_ops
    return saved


def _convert_weight(block, scope, new_ops, op, name: str,
                    scale_suffix: str) -> Optional[str]:
    v = block.vars.get(name)
    if v is None or not v.persistable:
        return None
    w = scope.find_var(name)
    if w is None:
        return None
    w = np.asarray(w)
    if w.ndim < 2 or str(w.dtype) not in ("float32", "float64"):
        return None
    axis = 1  # matmul-family weights quantize per output channel
    s = channel_absmax(w, axis)
    sb = s.reshape(_bshape(w, axis))
    wq = np.clip(np.round(w / sb * GRID_INT8), -GRID_INT8, GRID_INT8)
    scope.set(name, wq.astype(np.int8))
    if name in block.vars:
        block.vars[name].dtype = "int8"
    scale = name + scale_suffix
    if scale not in block.vars:
        block.create_var(scale, shape=[int(s.size)], dtype="float32",
                         persistable=True, stop_gradient=True)
    else:
        block.vars[scale].persistable = True
    scope.set(scale, s.astype(np.float32))
    deq = name + ".dequantized"
    if deq not in block.vars:
        block.create_var(deq, shape=list(w.shape), dtype="float32",
                         stop_gradient=True)
    from ..core.program import OpDesc
    # weight dequant: quant axis 1 IS the last axis of the 2-D weight,
    # so the freeze-pass op applies directly (Out = X*Scale/127)
    new_ops.append(OpDesc(
        "fake_channel_wise_dequantize_max_abs",
        {"X": [name], "Scales": [scale]}, {"Out": [deq]},
        {"quant_bits": [8], "quant_axis": w.ndim - 1}))
    return deq
