"""Drives `paddle_tpu.jit.TrainStep` as a user calls it.

Set-up builds ONE TrainStep over the configuration's model, loads the seeded
weights the reference module made, puts one fixed batch on the device and
drives the step through its first `compare_steps` steps (the first call
compiles). Those steps go through the window's own call and feed; their
losses, the first gradient as the optimizer got it (Adam's moment1 after one
step, over 1 - beta1) and the parameters' change over them are what `correct`
compares: a function of the seed and of fixed counts, whatever the window
holds later. The SAME object then runs the window: steps dispatched a fixed
few ahead of the device, one sync at the end.
"""
import collections
import functools
import time

import numpy as np

from benchmark import harness, traffic


@functools.lru_cache(None)
def _norm_fns():
    """Per-leaf norms of a tree, and of the difference of two, each one
    jitted call."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for n, v in t.items()})
    delta = jax.jit(lambda a, b: {n: jnp.sqrt(jnp.sum(jnp.square(
        a[n].astype(jnp.float32) - b[n]))) for n in b})
    return norms, delta


class Driver:
    def __init__(self, cfg, workload, seed, reference):
        self.cfg, self.wl, self.seed = cfg, workload, int(seed)
        self.ref = reference
        self.program = None      # what the timed path produced, for compare

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as pt
        from paddle_tpu import jit as pjit
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.models import bert
        from paddle_tpu.nn import transformer as tr

        cfg, wl = self.cfg, self.wl
        pt.seed(self.seed % (2 ** 31 - 1))   # the program's dropout stream
        fields = bert.BertConfig.__dataclass_fields__
        model = bert.BertForPretraining(bert.BertConfig(
            **{k: cfg[k] for k in fields if k in cfg}))
        # the step donates its state: the program gets a copy, the original
        # stays for the parameters' change
        self.weights = self.ref.make_weights(cfg, self.seed)
        pjit.load_state(model, jax.tree.map(jnp.copy, self.weights))
        o = cfg["optimizer"]
        opt = pt.optimizer.Adam(o["learning_rate"], beta1=o["beta1"],
                                beta2=o["beta2"], epsilon=o["epsilon"],
                                parameters=model.parameters())
        self.step = pjit.TrainStep(model, bert.pretraining_loss, opt,
                                   amp_dtype=cfg.get("amp_dtype"))
        self.batch_np = traffic.mlm_batch(wl, cfg, self.seed)
        ids, pos, mlm, nsp = jax.device_put(self.batch_np)
        self.inputs, self.labels = (ids, None, None, pos), (mlm, nsp)
        self.tokens_per_step = wl["batch"] * wl["seq_len"]

        tr.reset_attention_path_log()
        fa.reset_dropout_path_log()
        self.first_steps()

        paths = sorted(set(tr.attention_paths_taken()))
        drops = sorted(set(fa.dropout_paths_taken()))
        want_a, want_d = wl["expect_attention"], wl.get("expect_dropout")
        if paths != [want_a] or (want_d and drops != [want_d]):
            raise harness.BenchError(
                "the cell stands for attention %r with dropout %r; the step "
                "traced attention %r, dropout %r" % (want_a, want_d, paths,
                                                     drops))
        harness.say("attention traced %r, dropout %r" % (paths, drops))

    def first_steps(self):
        """The first `compare_steps` steps through the window's own call, and
        what `correct` reads from them."""
        wl, o = self.wl, self.cfg["optimizer"]
        norms, delta = _norm_fns()
        losses, grad_norm = [], None
        for i in range(wl["compare_steps"]):
            losses.append(self.call())
            if i == 0:
                beta1 = o["beta1"]
                m1 = {n: st["moment1"] for n, st in
                      self.step._opt_state.items()}
                grad_norm = {n: float(v) / (1.0 - beta1)
                             for n, v in norms(m1).items()}
        names = list(self.weights)
        state = {n: self.step._state[n] for n in names}
        update_norm = {n: float(v) for n, v in
                       delta(state, self.weights).items()}
        self.program = {"loss": [float(x) for x in losses],
                        "grad_norm": grad_norm, "update_norm": update_norm}
        harness.say("first %d losses %s" % (len(losses), self.program["loss"]))
        del self.weights    # remade from the seed for the reference, later

    def call(self):
        """The window's own call and feed."""
        with harness.span("step"):
            return self.step(self.inputs, self.labels)

    # -- the timed part ---------------------------------------------------------
    def _run_for(self, seconds):
        depth = int(self.wl["steps_in_flight"])
        inflight = collections.deque()
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            inflight.append(self.call())
            n += 1
            if len(inflight) > depth:
                inflight.popleft().block_until_ready()
        last = None
        while inflight:
            last = inflight.popleft()
        last.block_until_ready()           # the one sync that ends the window
        return n, time.perf_counter() - t0, float(last)

    def steady(self, seconds):
        self._run_for(seconds)

    def window(self, seconds):
        n, dt, last = self._run_for(seconds)
        ok = bool(np.isfinite(last))
        return {"end_to_end": {"train_tokens_per_s":
                               n * self.tokens_per_step / dt},
                "window_s": dt, "attempted": n, "failed": 0 if ok else n,
                "counters": {"steps": n, "last_loss": last}}

    def release(self):
        self.step = self.inputs = self.labels = None

    # -- correct ------------------------------------------------------------------
    def compare(self):
        cfg, wl = self.cfg, self.wl
        weights = self.ref.make_weights(cfg, self.seed)
        ref = self.ref.Reference(cfg, cfg["optimizer"]["learning_rate"],
                                 wl["reference_rows_per_block"])
        t0 = time.perf_counter()
        self.reference = ref.run(weights, self.batch_np, self.seed,
                                 steps=wl["compare_steps"])
        numbers, worst = self.ref.compare(self.program, self.reference)
        harness.say("reference: %d steps in %.1fs, losses %s"
                    % (wl["compare_steps"], time.perf_counter() - t0,
                       self.reference["loss"]))
        harness.say("worst leaves %s" % worst)
        return harness.held(numbers, self.ref)
