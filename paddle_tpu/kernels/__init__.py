"""Pallas TPU kernels for the hot ops.

TPU-native replacements for the reference's hand-written CUDA fused ops:
- flash_attention: /root/reference/paddle/fluid/operators/fused/
  multihead_matmul_op.cu (fused QK^T -> softmax -> PV attention)
- fused layer_norm: /root/reference/paddle/fluid/operators/layer_norm_op.cu
- fused softmax cross-entropy: /root/reference/paddle/fluid/operators/
  softmax_with_cross_entropy_op.cu

Each kernel exposes a pure-jnp reference path used on CPU (and by the
numpy-oracle OpTest harness); the Pallas path engages on TPU backends.
"""
import jax


def gspmd_will_partition() -> bool:
    """True while tracing, on a TPU, a computation that XLA's SPMD
    partitioner will split over a multi-device mesh. A Mosaic kernel
    cannot be partitioned automatically (jax refuses at lowering:
    "Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"), so there the kernels' public entries
    take their composed XLA path, which GSPMD can split. Inside a
    shard_map body the mesh axes are bound and the kernel runs per
    shard, so it stays. Off-TPU the kernels are interpreted into plain
    XLA ops, which partition like any other.

    The mesh is the ambient one (init_parallel_env's, or the
    ShardingPlan TrainStep/Executor activate while tracing) — the same
    trace-time observable MultiHeadAttention's fused-QKV bypass reads."""
    if jax.default_backend() != "tpu":
        return False
    from ..mesh.compat import in_named_axis
    from ..parallel.env import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return False
    return not any(in_named_axis(a) for a in mesh.axis_names)


from . import flash_attention  # noqa: E402,F401
from . import layer_norm  # noqa: E402,F401
