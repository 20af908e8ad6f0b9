"""BERT pretraining on the TPU throughput path: ONE fused
forward+backward+update XLA computation (jit.TrainStep) with AMP bf16
and optional dp x mp mesh sharding — the configuration bench.py scores
and chip_smoke.py drives at B=32 S=512 (speed: not measured at HEAD).

CPU toy scale by default. On a TPU host: set TOY=False; for multi-chip
set MESH to e.g. {"dp": 4, "mp": 2} — parameters shard over mp, the
batch over dp, XLA inserts the collectives (GSPMD)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as pt
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                    pretraining_loss)

TOY = True
MESH = None  # e.g. {"dp": 4, "mp": 2}


def main():
    pt.seed(0)
    if TOY:
        cfg = BertConfig(vocab_size=1000, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=256)
        B, S, M, steps, amp = 4, 128, 20, 5, None
    else:
        cfg = BertConfig()  # BERT-base
        B, S, M, steps, amp = 32, 512, 80, 100, "bfloat16"

    mesh = None
    rules = None
    if MESH:
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.env import init_parallel_env
        mesh = init_parallel_env(MESH).mesh
        H, I, V = (cfg.hidden_size, cfg.intermediate_size,
                   cfg.vocab_size)

        def rules(name, shape):
            # Megatron layout over the mp axis: FFN up column-sharded,
            # FFN down row-sharded (XLA inserts the activation
            # all-reduce), embedding table row-sharded. Everything else
            # replicates — without rules ALL params would replicate and
            # mp would just duplicate compute.
            if shape == (H, I):
                return P(None, "mp")
            if shape == (I, H):
                return P("mp", None)
            if shape == (V, H):
                return P("mp", None)
            return P()

    model = BertForPretraining(cfg)
    opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
    step = TrainStep(model, pretraining_loss, opt, amp_dtype=amp,
                     mesh=mesh, param_rules=rules)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    pos = np.stack([rng.choice(S, M, replace=False)
                    for _ in range(B)]).astype(np.int32)
    mlm = np.take_along_axis(ids, pos, 1).astype(np.int32)
    nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)
    for i in range(steps):
        loss = step((ids, None, None, pos), (mlm, nsp))
        if i % max(steps // 5, 1) == 0:
            print("step %d loss %.4f" % (i, float(loss)))


if __name__ == "__main__":
    main()
