"""Capture a TPU profiler trace of the BERT training step (run it on
the chip; nothing it prints has been measured at HEAD).

Writes an xplane trace dir to /tmp/bert_profile — inspect hot regions
with jax.profiler tooling or feed the xplane into the round's analysis.
The round-3 profile showed the forward healthy (~3.5ms/layer) and the
backward + embedding dW unaccounted; this captures exactly that split.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _bootstrap  # noqa: F401  (repo-root sys.path)
import numpy as np
import jax

import paddle_tpu as pt
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                    pretraining_loss)

OUT = "/tmp/bert_profile"


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    pt.seed(0)
    cfg = BertConfig()
    B, S, M = 32, 512, 80
    model = BertForPretraining(cfg)
    opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
    step = TrainStep(model, pretraining_loss, opt, amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = jax.device_put(rng.randint(0, cfg.vocab_size, (B, S))
                         .astype(np.int32))
    pos = jax.device_put(np.stack(
        [rng.choice(S, M, replace=False) for _ in range(B)])
        .astype(np.int32))
    mlm = jax.device_put(np.take_along_axis(
        np.asarray(ids), np.asarray(pos), 1).astype(np.int32))
    nsp = jax.device_put(rng.randint(0, 2, (B, 1)).astype(np.int32))
    inputs, labels = (ids, None, None, pos), (mlm, nsp)

    for _ in range(3):  # compile + cache both step signatures
        float(step(inputs, labels))

    with jax.profiler.trace(OUT):
        t0 = time.time()
        for _ in range(5):
            loss = step(inputs, labels)
        float(loss)
        dt = (time.time() - t0) / 5
    print("profiled 5 steps @ %.1f ms/step -> %s" % (dt * 1e3, OUT))


if __name__ == "__main__":
    main()
