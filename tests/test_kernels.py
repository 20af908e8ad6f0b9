"""Pallas kernel tests (interpret mode on CPU) vs composed-jnp oracles.

Mirrors the reference's OpTest contract (numpy oracle + gradient check,
/root/reference/python/paddle/fluid/tests/unittests/op_test.py:948,1236)
for the fused kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention import (attention_reference,
                                                flash_attention)
from paddle_tpu.kernels.layer_norm import layer_norm, layer_norm_reference


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    b, h, s, d = 2, 2, 256, 64
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)
    out = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# Blocks for the grad tests: one tile a (b, h), and several q- and
# k-tiles, so the backward's dQ is summed over key blocks in its scratch
# and its causal skip starts past the first q-block
ONE_TILE = (512, 512)


@pytest.mark.parametrize("blocks", [ONE_TILE, (64, 128)],
                         ids=["one_tile", "tiles_2x2"])
def test_flash_attention_causal_cross_length(blocks):
    # sq != sk: bottom-right-aligned causal mask must match the reference
    b, h, sq, sk, d = 1, 2, 128, 256, 64
    bq, bk = blocks
    q = _rand((b, h, sq, d), 0)
    k, v = _rand((b, h, sk, d), 1), _rand((b, h, sk, d), 2)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    g_f = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk)
        .sum(), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(lambda q, k, v: attention_reference(q, k, v, causal=True)
                   .sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_f, g_r):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("sq,sk,blocks", [(256, 128, ONE_TILE),
                                          (512, 256, (128, 128))],
                         ids=["one_tile", "tiles_4x2"])
def test_flash_attention_causal_sq_gt_sk(sq, sk, blocks):
    # sq > sk: leading q-rows see ZERO keys (bottom-right alignment);
    # their output is 0 and — the ADVICE r1 regression — their backward
    # must not blow up through exp(s - lse) with lse ~ -1e30. In the
    # tiled case the first two q-blocks are empty and the backward's
    # causal skip starts past them
    b, h, d = 1, 2, 64
    bq, bk = blocks
    q = _rand((b, h, sq, d), 0)
    k, v = _rand((b, h, sk, d), 1), _rand((b, h, sk, d), 2)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = attention_reference(q, k, v, causal=True)
    # empty rows output exactly 0 in both paths
    np.testing.assert_allclose(out[:, :, :sq - sk], 0.0, atol=1e-6)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    g_f = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk)
        .sum(), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(lambda q, k, v: attention_reference(q, k, v, causal=True)
                   .sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_f, g_r):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


def test_flash_attention_key_axis_size1_bias():
    # bias [...,1] on the key axis broadcasts instead of failing at
    # pallas trace time (ADVICE r1)
    b, h, s, d = 1, 2, 128, 64
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)
    bias = _rand((b, 1, s, 1), 3)
    out = flash_attention(q, k, v, bias=bias)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_bias_broadcast():
    b, h, s, d = 2, 2, 128, 64
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)
    # key-padding style mask [B, 1, 1, S]
    bias = jnp.where(_rand((b, 1, 1, s), 3) > 0, 0.0, -1e9)
    out = flash_attention(q, k, v, bias=bias)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("blocks", [ONE_TILE, (128, 128)],
                         ids=["one_tile", "tiles_2x2"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal, blocks):
    b, h, s, d = 1, 2, 256, 64
    bq, bk = blocks
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=bq,
                                block_k=bk) *
                _rand((b, h, s, d), 9)).sum()

    def f_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) *
                _rand((b, h, s, d), 9)).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("s,blocks,bias_q", [
    (128, ONE_TILE, 1), (256, (128, 128), 1), (256, (128, 128), 256)],
    ids=["one_tile", "tiles_2x2", "tiles_2x2_per_query_bias"])
def test_flash_attention_bias_grad(s, blocks, bias_q):
    # bias_needs_grad=True: the kernel's dQ/dK/dV beside the dbias
    # recompute in XLA
    b, h, d = 1, 2, 64
    bq, bk = blocks
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)
    bias = _rand((b, 1, bias_q, s), 3)

    def f_flash(q, k, v, bias):
        return (flash_attention(q, k, v, bias=bias, block_q=bq,
                                block_k=bk)).sum()

    def f_ref(q, k, v, bias):
        return (attention_reference(q, k, v, bias=bias)).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


def test_flash_attention_unaligned_fallback():
    # S not multiple of 128 -> composed path, still correct
    b, h, s, d = 1, 2, 100, 32
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)
    out = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_layer_norm_forward():
    x = _rand((4, 6, 256), 0)
    g, b = _rand((256,), 1), _rand((256,), 2)
    out = layer_norm(x, g, b)
    ref = layer_norm_reference(x, g, b)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_layer_norm_grads():
    x = _rand((8, 256), 0)
    g, b = _rand((256,), 1), _rand((256,), 2)
    w = _rand((8, 256), 5)

    gr_f = jax.grad(lambda x, g, b: (layer_norm(x, g, b) * w).sum(),
                    argnums=(0, 1, 2))(x, g, b)
    gr_r = jax.grad(lambda x, g, b: (layer_norm_reference(x, g, b) * w).sum(),
                    argnums=(0, 1, 2))(x, g, b)
    for a, b_ in zip(gr_f, gr_r):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


def test_layer_norm_unaligned_fallback():
    x = _rand((4, 100), 0)
    g, b = _rand((100,), 1), _rand((100,), 2)
    np.testing.assert_allclose(layer_norm(x, g, b),
                               layer_norm_reference(x, g, b),
                               atol=1e-5, rtol=1e-5)


def test_flash_attention_dropout_forward_stats():
    # dropout keeps the softmax denominator undropped and rescales kept
    # values by 1/keep_prob, so E[out] matches the dropless output
    b, h, s, d = 2, 2, 256, 64
    q, k, v = _rand((b, h, s, d), 0), _rand((b, h, s, d), 1), \
        _rand((b, h, s, d), 2)
    rng = jax.random.PRNGKey(7)
    out = flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=rng)
    base = flash_attention(q, k, v)
    # must actually drop something
    assert not np.allclose(np.asarray(out), np.asarray(base))
    # expectation check: averaged over the whole tensor the dropped
    # output tracks the dropless one
    np.testing.assert_allclose(float(out.mean()), float(base.mean()),
                               atol=5e-3)


def test_flash_attention_dropout_matches_masked_oracle():
    # the kernel consumes a precomputed keep-mask; rebuild the same mask
    # and apply the identical semantics composed to get an exact oracle
    from paddle_tpu.kernels.flash_attention import dropout_keep_mask
    b, h, s, d = 1, 2, 256, 64
    q, k, v = _rand((b, h, s, d), 3), _rand((b, h, s, d), 4), \
        _rand((b, h, s, d), 5)
    rate = 0.25
    rng = jax.random.PRNGKey(11)
    out = flash_attention(q, k, v, dropout_rate=rate, dropout_rng=rng)

    keep = dropout_keep_mask(rng, rate, (b, h, s, s), q.dtype)
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs * keep / (1.0 - rate)
    ref = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("blocks", [ONE_TILE, (128, 128)],
                         ids=["one_tile", "tiles_2x2"])
def test_flash_attention_dropout_grads(blocks):
    from paddle_tpu.kernels.flash_attention import dropout_keep_mask
    b, h, s, d = 1, 2, 256, 64
    bq, bk = blocks
    q, k, v = _rand((b, h, s, d), 6), _rand((b, h, s, d), 7), \
        _rand((b, h, s, d), 8)
    rate = 0.2
    rng = jax.random.PRNGKey(13)
    w = _rand((b, h, s, d), 9)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, dropout_rate=rate,
                                dropout_rng=rng, block_q=bq,
                                block_k=bk) * w).sum()

    keep = dropout_keep_mask(rng, rate, (b, h, s, s), q.dtype)

    def f_ref(q, k, v):
        scale = 1.0 / np.sqrt(d)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        probs = jax.nn.softmax(scores, axis=-1)
        probs = probs * keep / (1.0 - rate)
        return (jnp.einsum("bhqk,bhkd->bhqd", probs, v) * w).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-4)


def test_flash_block_divisor_fallback():
    """Non-512-divisible long seqs must still take the Pallas path: the
    entry shrinks blocks to divisors instead of bouncing S=1280 to the
    composed fallback (interpret mode exercises the same routing)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (attention_reference,
                                                    flash_attention)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 1280, 64) * 0.1, jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 1280, 64) * 0.1, jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 1280, 64) * 0.1, jnp.float32)
    out = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="in-kernel PRNG dropout needs the real TPU "
                           "(pltpu.prng has no interpret-mode impl)")
def test_flash_inkernel_dropout_tpu():
    """Delegates to the standalone parity script, the SAME check
    chip_smoke.py runs on the chip (tests/conftest.py holds every
    pytest session to the CPU backend, so this test only runs where
    that is lifted)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "inkernel_parity",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "inkernel_parity.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.check_inkernel_dropout_parity()


@pytest.mark.parametrize("S,block", [(256, 128), (1024, 512)],
                         ids=["tiles_2x2", "s1024_tiles_2x2"])
def test_flash_bias_needs_grad_false_matches_reference(S, block):
    """bias_needs_grad=False must not change q/k/v grads (the dbias
    recompute is skipped, its cotangent is zeros) — the padding-mask
    contract that makes in-kernel dropout eligible with a bias. The
    second case is the tiling of scripts/inkernel_parity.py."""
    from paddle_tpu.kernels.flash_attention import (attention_reference,
                                                    flash_attention)
    rng = np.random.RandomState(2)
    B, H, D = 1, 2, 64
    q = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.float32)
    mask = np.zeros((B, 1, 1, S), np.float32)
    mask[..., -32:] = -1e9
    bias = jnp.asarray(mask)

    def loss_flash(q, k, v, b):
        return jnp.sum(flash_attention(q, k, v, bias=b, sm_scale=0.125,
                                       block_q=block, block_k=block,
                                       bias_needs_grad=False) ** 2)

    def loss_ref(q, k, v, b):
        return jnp.sum(attention_reference(q, k, v, bias=b,
                                           sm_scale=0.125) ** 2)

    gq, gk, gv, gb = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v,
                                                                bias)
    rq, rk, rv, _ = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               atol=2e-4, rtol=2e-3)
    assert np.all(np.asarray(gb) == 0.0)  # declared non-differentiable


def test_attention_core_mask_is_stop_gradiented():
    """The nn router treats attn_mask as non-differentiable by contract
    (both composed and flash paths)."""
    from paddle_tpu.nn.transformer import _attention_core
    rng = np.random.RandomState(3)
    B, S, H, D = 2, 64, 4, 16
    q = jnp.asarray(rng.randn(B, S, H, D) * 0.1, jnp.float32)
    mask = jnp.zeros((B, 1, 1, S), jnp.float32)

    def loss(m):
        return jnp.sum(_attention_core(q, q, q, m, 0.0, False) ** 2)

    g = jax.grad(loss)(mask)
    assert np.all(np.asarray(g) == 0.0)


# ---------------------------------------------------------------------------
# Mosaic kernels under a GSPMD mesh (found by the 4-chip compile, PR 22)
# ---------------------------------------------------------------------------

def test_mosaic_kernels_yield_to_a_gspmd_mesh(monkeypatch):
    """On a TPU, jax refuses to partition a Mosaic kernel automatically,
    so under an ambient multi-device mesh the kernels' public entries
    trace their composed path; inside a shard_map body (axes bound) and
    with no mesh they keep the kernel. The CPU sees none of this, so the
    test steers the backend query itself and only TRACES."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.kernels import gspmd_will_partition
    from paddle_tpu.mesh.compat import shard_map
    from paddle_tpu.nn.transformer import routes_to_flash
    from paddle_tpu.parallel import env
    x = jnp.zeros((16, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)
    qkv = jnp.zeros((2, 2, 512, 64), jnp.float32)

    def traced(fn, *args):
        # a fresh callable each time: jax caches traces per function
        return "pallas_call" in str(jax.make_jaxpr(
            lambda *a: fn(*a))(*args))

    prev = env._env.mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        env._env.mesh = None
        assert not gspmd_will_partition()
        assert traced(layer_norm, x, g, g)
        assert traced(flash_attention, qkv, qkv, qkv)
        assert routes_to_flash(1024, 64)
        mesh = env.init_parallel_env({"dp": 2, "mp": 2},
                                     devices=jax.devices()[:4]).mesh
        assert gspmd_will_partition()
        assert not traced(layer_norm, x, g, g)
        assert not traced(flash_attention, qkv, qkv, qkv)
        assert not routes_to_flash(1024, 64)
        per_shard = shard_map(
            lambda a: layer_norm(a, g, g), mesh,
            in_specs=P(("dp", "mp")), out_specs=P(("dp", "mp")),
            check_vma=False)
        assert traced(per_shard, jnp.zeros((64, 128), jnp.float32))
    finally:
        env._env.mesh = prev


# ---------------------------------------------------------------------------
# ragged paged attention (PR 10): mixed prefill+decode batches
# ---------------------------------------------------------------------------

from paddle_tpu.generation import (DecoderConfig, KVCacheManager,  # noqa: E402
                                   forward_full, forward_paged,
                                   init_params)
from paddle_tpu.kernels.paged_attention import (  # noqa: E402
    paged_attention_reference, ragged_paged_attention,
    ragged_paged_attention_pallas, ragged_paged_attention_reference)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _ragged_case(seed=0):
    rng = np.random.default_rng(seed)
    b, cq, h, d, bs, n, m = 4, 4, 4, 8, 4, 16, 4
    q = jnp.asarray(rng.normal(size=(b, cq, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n, bs, h, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n, bs, h, d)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, n, (b, m)), jnp.int32)
    # mixed batch: full chunk, decode single, short chunk, decode single
    q_lens = jnp.asarray([4, 1, 2, 1], jnp.int32)
    ctx = jnp.asarray([5, 9, 0, 3], jnp.int32)
    return q, kp, vp, tbl, q_lens, ctx


def test_ragged_reference_bitwise_matches_per_token_decode():
    """Every real query row of the ragged reference equals the Cq == 1
    decode path at the same absolute position, bit for bit — chunked
    prefill and single-token decode share one numerics contract."""
    q, kp, vp, tbl, q_lens, ctx = _ragged_case()
    out = ragged_paged_attention_reference(q, kp, vp, tbl, q_lens, ctx)
    for i in range(q.shape[0]):
        for j in range(int(q_lens[i])):
            one = paged_attention_reference(
                q[i:i + 1, j], kp, vp, tbl[i:i + 1], ctx[i:i + 1] + j + 1)
            assert np.array_equal(_bits(out[i, j]), _bits(one[0])), \
                "row %d query %d diverged" % (i, j)


def test_ragged_pallas_interpret_matches_reference():
    q, kp, vp, tbl, q_lens, ctx = _ragged_case(seed=3)
    ref = ragged_paged_attention_reference(q, kp, vp, tbl, q_lens, ctx)
    pal = ragged_paged_attention_pallas(q, kp, vp, tbl, q_lens, ctx)
    # compare only real rows: fully-masked rows intentionally differ
    # (reference degrades to a uniform average, the kernel emits 0)
    for i in range(q.shape[0]):
        for j in range(int(q_lens[i])):
            np.testing.assert_allclose(
                np.asarray(pal[i, j]), np.asarray(ref[i, j]),
                atol=2e-5, rtol=2e-5)


def test_ragged_kernel_form_seam():
    """A kernel_form pin routes the ragged entry exactly like the
    decode entry; outside the block XLA:CPU takes the reference form,
    to the bit."""
    from paddle_tpu.kernels.paged_attention import kernel_form
    q, kp, vp, tbl, q_lens, ctx = _ragged_case(seed=7)
    ref = ragged_paged_attention_reference(q, kp, vp, tbl, q_lens, ctx)
    with kernel_form("pallas"):
        pal = ragged_paged_attention(q, kp, vp, tbl, q_lens, ctx)
    for i in range(q.shape[0]):
        for j in range(int(q_lens[i])):
            np.testing.assert_allclose(
                np.asarray(pal[i, j]), np.asarray(ref[i, j]),
                atol=2e-5, rtol=2e-5)
    routed = ragged_paged_attention(q, kp, vp, tbl, q_lens, ctx)
    assert np.array_equal(_bits(routed), _bits(ref))


# ---------------------------------------------------------------------------
# the grouped kernel (PR 32): G table entries a step of its loop, and no block
# past a slot's length is read. Interpret mode, the engine's stacked
# flat pools, against the reference form.
# ---------------------------------------------------------------------------

from paddle_tpu import quant as _quant  # noqa: E402
from paddle_tpu.kernels import paged_attention as _pa  # noqa: E402

_G_BS, _G_M, _G_H, _G_D, _G_LAYERS, _G_N = 4, 5, 2, 8, 3, 26
_POOL_DTYPES = pytest.mark.parametrize(
    "pool_dtype", ["float32", "bfloat16", "int8"])


@pytest.fixture
def groups_of_two(monkeypatch):
    """Blocks of 4 positions, G = 2 (a group is 8 positions), tables
    of 5 entries (not a multiple of G): the derivation that gives 8
    at the cells' sizes, held to toy tiles."""
    monkeypatch.setattr(_pa, "_MAX_STEP_TOKENS", 2 * _G_BS)
    assert _pa.blocks_per_step(_G_BS, _G_H * _G_D * 4, _G_M) == 2


def _stacked_pools(rng, pool_dtype):
    """(k, v, k_scales, v_scales): `[layers, N, bs, H * D]` pools as the
    engine holds them, float32 / bfloat16 / int8 with absmax scales."""
    shape = (_G_LAYERS, _G_N, _G_BS, _G_H, _G_D)
    flat = shape[:3] + (_G_H * _G_D,)
    kf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    if pool_dtype == "int8":
        kq, ks = _quant.quantize_kv_rows(kf, jnp.int8)
        vq, vs = _quant.quantize_kv_rows(vf, jnp.int8)
        return kq.reshape(flat), vq.reshape(flat), ks, vs
    dt = jnp.dtype(pool_dtype)
    return kf.reshape(flat).astype(dt), vf.reshape(flat).astype(dt), \
        None, None


def _tables(positions, parked=()):
    """Distinct blocks for every table entry (so a block past a slot's
    length is nobody's live block); a parked slot holds the trash
    block, 0, at position 0."""
    b = len(positions)
    tbl = 1 + np.arange(b * _G_M, dtype=np.int32).reshape(b, _G_M)
    assert tbl.max() < _G_N
    for i in parked:
        tbl[i] = 0
    return jnp.asarray(tbl)


def _both_forms(q, pools, tbl, positions, layer=1):
    kp, vp, ks, vs = pools
    ctx = jnp.asarray(positions, jnp.int32) + 1
    kw = dict(k_scales=ks, v_scales=vs, layer=layer)
    return (np.asarray(_pa.paged_attention_pallas(q, kp, vp, tbl, ctx, **kw)),
            np.asarray(paged_attention_reference(q, kp, vp, tbl, ctx, **kw)))


@_POOL_DTYPES
@pytest.mark.parametrize("position", [5, 3, 4, 9, 7, 8, 19, 0], ids=[
    "inside_a_block", "last_of_a_block", "first_of_a_block",
    "inside_the_second_group", "last_of_a_group", "first_of_a_group",
    "table_full", "parked"])
def test_grouped_kernel_matches_reference(groups_of_two, position,
                                          pool_dtype):
    """The slot under test between two batch-mates of other lengths,
    the last a parked one."""
    rng = np.random.default_rng(100 + position)
    positions = [11, position, 0]
    parked = (2,) + ((1,) if position == 0 else ())
    q = jnp.asarray(rng.normal(size=(3, _G_H, _G_D)), jnp.float32)
    pal, ref = _both_forms(q, _stacked_pools(rng, pool_dtype),
                           _tables(positions, parked), positions)
    np.testing.assert_allclose(pal, ref, atol=2e-5, rtol=2e-5)


@_POOL_DTYPES
def test_grouped_kernel_chunk_mates_share_a_table(groups_of_two,
                                                  pool_dtype):
    """Four slots of one prefill chunk at consecutive positions over a
    group's edge, one table between them, and a decode lane."""
    rng = np.random.default_rng(7)
    positions = [6, 7, 8, 9, 13]
    tbl = np.array(_tables(positions))
    tbl[:4] = tbl[0]
    q = jnp.asarray(rng.normal(size=(5, _G_H, _G_D)), jnp.float32)
    pal, ref = _both_forms(q, _stacked_pools(rng, pool_dtype),
                           jnp.asarray(tbl), positions)
    np.testing.assert_allclose(pal, ref, atol=2e-5, rtol=2e-5)


@_POOL_DTYPES
def test_grouped_kernel_reads_no_block_past_a_length(groups_of_two,
                                                     pool_dtype):
    """Every block past each slot's length poisoned (NaN rows; an int8
    pool's scales NaN and its rows 127): the outputs are finite and
    EQUAL, to the bit, those over the clean pool — nothing past the
    length was used. (The reference form reads them all: NaN.)"""
    rng = np.random.default_rng(11)
    positions = [5, 8, 0, 19, 3]
    tbl = _tables(positions, parked=(2,))
    pools = _stacked_pools(rng, pool_dtype)
    q = jnp.asarray(rng.normal(size=(5, _G_H, _G_D)), jnp.float32)
    clean, ref = _both_forms(q, pools, tbl, positions)
    dead = np.ones(_G_N, bool)
    for row, pos in zip(np.asarray(tbl), positions):
        dead[row[:pos // _G_BS + 1]] = False

    def poison(pool, value):
        return None if pool is None else \
            pool.at[:, np.flatnonzero(dead)].set(value)
    bad = 127 if pool_dtype == "int8" else np.nan
    poisoned = (poison(pools[0], bad), poison(pools[1], bad),
                poison(pools[2], np.nan), poison(pools[3], np.nan))
    pal, ref_poisoned = _both_forms(q, poisoned, tbl, positions)
    assert np.isnan(ref_poisoned).any()
    assert np.isfinite(pal).all()
    assert np.array_equal(_bits(pal), _bits(clean))
    np.testing.assert_allclose(pal, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
def test_grouped_kernel_takes_a_traced_layer_under_scan(groups_of_two,
                                                        pool_dtype):
    """The looped family's call: the cache layer a scalar of the scan."""
    rng = np.random.default_rng(13)
    positions = [9, 2, 16]
    tbl = _tables(positions)
    kp, vp, _, _ = pools = _stacked_pools(rng, pool_dtype)
    q = jnp.asarray(rng.normal(size=(3, _G_H, _G_D)), jnp.float32)
    ctx = jnp.asarray(positions, jnp.int32) + 1

    def body(carry, layer):
        return carry, _pa.paged_attention_pallas(q, kp, vp, tbl, ctx,
                                                 layer=layer)
    _, outs = jax.jit(lambda: jax.lax.scan(
        body, 0, jnp.arange(_G_LAYERS, dtype=jnp.int32)))()
    for layer in range(_G_LAYERS):
        _, ref = _both_forms(q, pools, tbl, positions, layer=layer)
        np.testing.assert_allclose(np.asarray(outs[layer]), ref,
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_size,row_bytes,max_blocks,want", [
    (16, 768 * 4, 64, 8),       # gpt2_124m: float32 rows of 768
    (16, 2048 * 2, 32, 8),      # ouro_2_6b: bfloat16 rows of 2,048
    (16, 768, 64, 8),           # an int8 pool of GPT-2's rows
    (16, 8192 * 4, 64, 2),      # a row of 32 KB: the memory budget
    (16, 768 * 4, 5, 4),        # a table narrower than a group
    (64, 1024, 16, 2),          # blocks of 64 positions: the lanes
    (512, 1024, 16, 1)], ids=[
        "gpt2", "ouro", "int8", "wide_rows", "narrow_table",
        "long_blocks", "block_over_the_step"])
def test_blocks_per_step_follows_the_shapes(block_size, row_bytes,
                                            max_blocks, want):
    g = _pa.blocks_per_step(block_size, row_bytes, max_blocks)
    assert g == want
    # K and V tiles, two buffers each, inside the budget (one block a
    # step is the floor whatever it takes)
    assert g == 1 or 4 * g * block_size * row_bytes <= _pa._KV_VMEM_BUDGET


# forward_paged's mixed step against forward_full, float32 on both
# sides over a key axis of the same width: XLA:CPU picks a matmul's
# tiling from the batch's shape ([5, h] slots against [1, 32, h]), so
# the last bits differ. Largest gap measured over 24 seeds of weights
# and tokens: 1.43e-6 on logits up to 4.2; the limit is ten times that.
# The planted fault (every slot's mask one lane short, so a token does
# not see its own key) reads 2.7 to 4.4 over the same seeds, and 0.2 at
# its least at any one position.
MIXED_PARITY_ATOL = 1.5e-5


@pytest.mark.parametrize("fault", [None, "mask_one_lane_short"])
def test_chunked_prefill_mixed_batch_bitwise_vs_forward_full(
        fault, monkeypatch):
    """PR-5's paged==full parity pin extended to chunked prefill: a
    prompt streamed through the mixed step in 4-token chunks — sharing
    its batch with a concurrently DECODING sequence — produces, at
    every prompt position and every decode step, logits within
    MIXED_PARITY_ATOL of a full-context forward_full recompute (no
    longer bit for bit: see there). With the fault planted the same
    comparisons read ten times over the limit."""
    from paddle_tpu.generation import model as gen_model
    if fault:
        real = gen_model.paged_attention
        monkeypatch.setattr(
            gen_model, "paged_attention",
            lambda q, k, v, tables, ctx, **kw: real(q, k, v, tables,
                                                    ctx - 1, **kw))
    gaps = []

    def check(got, want, what):
        if fault is None:
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=MIXED_PARITY_ATOL,
                                       err_msg=what)
        gaps.append(np.abs(np.asarray(got - want)).max())
    cfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                        max_seq_len=32)
    params = init_params(cfg, seed=0)
    bs, nblocks, t_slots = 4, 32, 5
    m = -(-cfg.max_seq_len // bs)
    lanes = m * bs
    rng = np.random.default_rng(5)
    pa = [int(x) for x in rng.integers(1, cfg.vocab_size, 13)]
    pb = [int(x) for x in rng.integers(1, cfg.vocab_size, 5)]
    sb = 32
    ff = jax.jit(lambda p, t, l: forward_full(cfg, p, t, l,
                                              attn_lanes=lanes))

    def oracle(tokens):
        padded = np.zeros((1, sb), np.int32)
        padded[0, :len(tokens)] = tokens
        return ff(params, jnp.asarray(padded),
                  jnp.asarray([len(tokens)], np.int32))[0][0]

    step = jax.jit(lambda p, k, v, tb, c, x: forward_paged(
        cfg, p, k, v, tb, c, x))
    mgr = KVCacheManager(nblocks, bs)
    shape = (cfg.layers, nblocks, bs, cfg.heads, cfg.head_dim)
    kp = jnp.zeros(shape, jnp.float32)
    vp = jnp.zeros(shape, jnp.float32)
    mgr.alloc("A", mgr.blocks_for_tokens(len(pa) + 1))
    mgr.alloc("B", mgr.blocks_for_tokens(len(pb) + 6))
    ta = np.asarray(mgr.table("A", m), np.int32)
    tb_ = np.asarray(mgr.table("B", m), np.int32)

    tables = np.zeros((t_slots, m), np.int32)
    pos = np.zeros((t_slots,), np.int32)
    toks = np.zeros((t_slots,), np.int32)
    # step 0: B's whole prompt rides in as one chunk
    for j in range(len(pb)):
        tables[j], pos[j], toks[j] = tb_, j, pb[j]
    logits, kp, vp = step(params, kp, vp, jnp.asarray(tables),
                          jnp.asarray(pos), jnp.asarray(toks))
    for j in range(len(pb)):
        check(logits[j], oracle(pb[:j + 1]), "B's prompt at %d" % j)
    btoks = pb + [int(np.argmax(np.asarray(logits[len(pb) - 1])))]
    # A's 13-token prompt streams in chunks of 4 while B greedy-decodes
    filled = 0
    while filled < len(pa):
        take = min(4, len(pa) - filled)
        tables[:], pos[:], toks[:] = 0, 0, 0
        tables[0], pos[0], toks[0] = tb_, len(btoks) - 1, btoks[-1]
        for j in range(take):
            tables[1 + j] = ta
            pos[1 + j] = filled + j
            toks[1 + j] = pa[filled + j]
        logits, kp, vp = step(params, kp, vp, jnp.asarray(tables),
                              jnp.asarray(pos), jnp.asarray(toks))
        check(logits[0], oracle(btoks),
              "decode lane diverged while chunk [%d:%d) prefilled"
              % (filled, filled + take))
        for j in range(take):
            check(logits[1 + j], oracle(pa[:filled + j + 1]),
                  "chunked prefill diverged at position %d"
                  % (filled + j))
        btoks.append(int(np.argmax(np.asarray(logits[0]))))
        filled += take
    if fault:
        assert max(gaps) >= 10 * MIXED_PARITY_ATOL, gaps
