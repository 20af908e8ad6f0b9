"""`correct` has been shown to fail: the lower-precision controls and the
planted faults, at a size a test run can hold.

The fault tests drive the whole run (the look for a chip skipped) with the
timed path broken UNDERNEATH the benchmark, inside the program, and see
`correct` come out false. The real cells' readings of the same controls and
faults, on the chip at the cells' own sizes, are in PERF.md section 6.
"""
import json

import numpy as np
import pytest

from helpers import DATA, SERVE, TRAIN, rehearse


def _over(r):
    return [n for n, v in r["compared"].items() if not v["value"] <= v["limit"]]


def test_train_state_returned_unchanged(monkeypatch):
    from paddle_tpu.jit import TrainStep
    real = TrainStep.__call__

    def frozen(self, inputs, labels):
        import jax
        import jax.numpy as jnp
        built = self._step_fn is not None
        keep = jax.tree.map(jnp.copy, (self._state, self._opt_state,
                                       self._lr_step)) if built else None
        loss = real(self, inputs, labels)
        if built:
            self._state, self._opt_state, self._lr_step = keep
        return loss
    monkeypatch.setattr(TrainStep, "__call__", frozen)
    r = rehearse(TRAIN)
    assert r["correct"] is False
    assert "median_update_gap" in _over(r)


def test_train_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.jit import TrainStep
    real = TrainStep.__call__

    def half(self, inputs, labels):
        def cut(t):
            return tuple(None if x is None else x[:x.shape[0] // 2]
                         for x in t)
        return real(self, cut(inputs), cut(labels))
    monkeypatch.setattr(TrainStep, "__call__", half)
    r = rehearse(TRAIN)
    assert r["correct"] is False
    assert {"head_grad_gap", "head_bias_grad_gap"} <= set(_over(r))


def test_train_attention_output_zeroed(monkeypatch):
    """An encoder fault: every layer's attention core returns zeros, as a
    kernel that wrote nothing would. No gradient reaches the q, k, v and
    output projections."""
    from paddle_tpu.nn import transformer as tr
    real = tr._attention_core

    def zeroed(*a, **k):
        return real(*a, **k) * 0.0
    monkeypatch.setattr(tr, "_attention_core", zeroed)
    r = rehearse(TRAIN)
    assert r["correct"] is False
    assert "encoder_grad_shortfall" in _over(r)
    assert r["compared"]["encoder_grad_shortfall"]["value"] == 1.0


def test_serve_a_token_altered_where_it_is_produced(monkeypatch):
    from paddle_tpu.generation.engine import GenerationEngine
    real = GenerationEngine._retire

    def altered(self, lane, reason):
        res = real(self, lane, reason)
        res.tokens[len(res.tokens) // 2] = \
            (res.tokens[len(res.tokens) // 2] + 1) % self.cfg.vocab_size
        return res
    monkeypatch.setattr(GenerationEngine, "_retire", altered)
    r = rehearse(SERVE)
    assert r["correct"] is False
    assert _over(r) == ["served_logit_gap"]


def test_serve_a_failed_request_is_not_correct(monkeypatch):
    from paddle_tpu.generation.engine import GenerationEngine
    real = GenerationEngine._retire
    seen = []

    def short(self, lane, reason):
        res = real(self, lane, reason)
        seen.append(1)
        if len(seen) % 7 == 0:
            del res.tokens[-1]
        return res
    monkeypatch.setattr(GenerationEngine, "_retire", short)
    r = rehearse(SERVE)
    assert r["failed"] > 0 and r["correct"] is False


def _cfg(name):
    return json.load(open("%s/configs/%s.json" % (DATA, name)))


def _reference(name):
    from benchmark import harness
    from helpers import SPEC
    return harness.Files(SPEC, [DATA]).load_module("references", name)


@pytest.mark.parametrize("precision", ["int8", "fp8"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_train_control_is_not_correct(seed, precision):
    """The reference put in the program's place, computed in int8 or float8."""
    from benchmark import traffic
    R = _reference("toy_bert")
    cfg = _cfg("toy_bert")
    wl = json.load(open("%s/workloads/%s.json" % (DATA, TRAIN)))
    w = R.make_weights(cfg, seed)
    batch = traffic.mlm_batch(wl, cfg, seed)
    lr = cfg["optimizer"]["learning_rate"]
    ref = R.Reference(cfg, lr, 2).run(w, batch, seed)
    ctl = R.Reference(cfg, lr, 2, precision=precision).run(w, batch, seed)
    other_blocks = R.Reference(cfg, lr, 4).run(w, batch, seed)
    sound, _ = R.compare(other_blocks, ref)
    assert all(sound[n] <= R.LIMITS[n] for n in R.LIMITS)
    got, _ = R.compare(ctl, ref)
    assert got["head_grad_gap"] > 3 * R.LIMITS["head_grad_gap"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_train_encoder_fault_in_the_reference_reads_a_dead_leaf(seed):
    """The reference put in the program's place with every layer's attention
    output zeroed: the shortfall reads 1 exactly, whatever the size."""
    from benchmark import traffic
    R = _reference("toy_bert")
    cfg = _cfg("toy_bert")
    wl = json.load(open("%s/workloads/%s.json" % (DATA, TRAIN)))
    w = R.make_weights(cfg, seed)
    batch = traffic.mlm_batch(wl, cfg, seed)
    lr = cfg["optimizer"]["learning_rate"]
    ref = R.Reference(cfg, lr, 2).run(w, batch, seed)
    bad = R.Reference(cfg, lr, 2, fault="attention_zeroed").run(w, batch, seed)
    got, worst = R.compare(bad, ref)
    assert got["encoder_grad_shortfall"] == 1.0
    assert "self_attn" in worst["encoder_grad_shortfall"]


def test_train_nought_gradients_are_left_out_of_the_change():
    from benchmark.references import bert_base_mlm as R
    ref = {"loss": [1.0], "grad_norm": {"a": 1.0, "b": 1.0, "k_bias": 1e-9},
           "update_norm": {"a": 1.0, "b": 1.0, "k_bias": 1.0}}
    prog = {"loss": [1.0], "grad_norm": {"a": 1.0, "b": 1.0, "k_bias": 2e-9},
            "update_norm": {"a": 1.0, "b": 1.0, "k_bias": 0.1}}
    got, _ = R.compare(prog, ref)
    assert got["median_update_gap"] == 0 and got["update_norm_gap"] == 0
    assert got["nought_grad_share"] == 2e-9 and "head_grad_gap" not in got


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_serve_control_is_not_correct(seed):
    """At each position of the same prompts and tokens, the token the
    bfloat16 forward puts first, read against the float32 logits."""
    R = _reference("toy_gpt2")
    cfg = dict(_cfg("toy_gpt2"), n_positions=256)
    w = R.make_weights(cfg, seed)
    ref = R.Reference(cfg, pad_to=256, new_tokens=64)
    rng = np.random.RandomState(seed)
    sound, control = [], []
    for _ in range(6):
        prompt = rng.randint(0, cfg["vocab_size"], 150)
        # greedy decoding BY the reference: its own first choices
        served = []
        for _ in range(4):
            served.append(int(_first(ref, w, prompt, served)))
        tail = rng.randint(0, cfg["vocab_size"], 60).tolist()
        sound.append(ref.gaps(w, prompt, served))
        control.append(ref.gaps(w, prompt, served + tail, control="bfloat16"))
    assert R.compare(sound)["served_logit_gap"] == 0
    assert R.compare(control)["served_logit_gap"] > \
        R.LIMITS["served_logit_gap"]


def _first(ref, w, prompt, served):
    """The reference's first choice after prompt + served."""
    import jax.numpy as jnp
    toks = np.zeros((ref.pad_to,), np.int32)
    seq = list(prompt) + list(served)
    toks[:len(seq)] = seq
    lg = ref._logits(w, jnp.asarray(toks), jnp.int32(len(seq) - 1),
                     precision="float32")
    return np.asarray(lg)[0].argmax()
