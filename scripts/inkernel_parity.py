"""In-kernel flash-attention PRNG dropout parity check — needs a TPU.

Interpret mode cannot reproduce the hardware PRNG stream, so this is
the in-kernel dropout path's only oracle: determinism, forward/backward
mask agreement by finite differences, and the bias+dropout combination.
chip_smoke.py runs it in its train phase on every chip run; this file
also runs alone (`python scripts/inkernel_parity.py`), because
tests/conftest.py holds every pytest session to the CPU backend.
Exit 0 = parity holds.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _bootstrap  # noqa: F401  (repo-root sys.path)
import numpy as np


def check_inkernel_dropout_parity():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.flags import set_flags
    from paddle_tpu.kernels.flash_attention import flash_attention

    if jax.default_backend() != "tpu":
        raise RuntimeError("parity check needs the real TPU backend, "
                           "got %r" % jax.default_backend())
    from paddle_tpu.flags import get_flags
    prior = get_flags(["FLAGS_flash_inkernel_dropout"])
    set_flags({"FLAGS_flash_inkernel_dropout": True})
    try:
        B, H, S, D = 2, 4, 1024, 64
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.bfloat16)
        key = jax.random.PRNGKey(7)

        f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, dropout_rate=0.3, dropout_rng=key))
        o1, o2 = f(q, k, v), f(q, k, v)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        o_ref = flash_attention(q, k, v)
        err = np.abs(np.asarray(o1, np.float32)
                     - np.asarray(o_ref, np.float32)).mean()
        base = np.abs(np.asarray(o_ref, np.float32)).mean() + 1e-6
        assert err / base < 1.5, (err, base)

        # fwd/bwd regenerate the SAME mask: directional finite
        # difference must match the custom-vjp gradient
        qf = q.astype(jnp.float32)
        R = jnp.asarray(rng.randn(B, H, S, D) * 0.01, jnp.float32)

        def scalar_f(qq):
            out = flash_attention(qq, k.astype(jnp.float32),
                                  v.astype(jnp.float32),
                                  dropout_rate=0.3, dropout_rng=key)
            return jnp.sum(out.astype(jnp.float32) * R)

        g = jax.grad(scalar_f)(qf)
        assert np.isfinite(np.asarray(g)).all()
        dq_dir = jnp.asarray(rng.randn(B, H, S, D) * 1.0, jnp.float32)
        eps = 1e-2
        fd = (float(scalar_f(qf + eps * dq_dir))
              - float(scalar_f(qf - eps * dq_dir))) / (2 * eps)
        analytic = float(jnp.sum(g * dq_dir))
        np.testing.assert_allclose(fd, analytic, rtol=5e-2, atol=1e-3)

        # with a padding bias present (bias_needs_grad=False) the seed
        # path must still be numerically sane at the scored config
        mask = np.zeros((B, 1, 1, S), np.float32)
        mask[..., -S // 8:] = -1e9
        ob = flash_attention(q, k, v, bias=jnp.asarray(mask),
                             dropout_rate=0.3, dropout_rng=key,
                             bias_needs_grad=False)
        assert np.isfinite(np.asarray(ob, np.float32)).all()
    finally:
        set_flags(prior)  # restore the shipped default, whatever it is


if __name__ == "__main__":
    check_inkernel_dropout_parity()
    print("in-kernel dropout parity OK")
