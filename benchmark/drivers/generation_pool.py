"""Drives `GenerationPool(GenerationEngine(...)).submit(...)` under a closed
loop of clients.

Set-up makes the seeded weights, builds the engine with what the
configuration's deployment fixes (lanes, KV pool) and nothing more, warms it
with `engine.warmup()`, starts the clients and lets them run until every lane
has been occupied and the first requests have completed. The clients run on
through the window. Its tokens per second count every generated token that
reached the host inside it, of requests that ended and of requests still in
flight at either edge (`RequestTrace.tokens`, which the engine stamps token by
token): counted by whole requests the rate moved in steps of 1.5 %, one
request in 68, with whichever request ended just before or after the close (my
chip runs, PR 26). The latency sample is the requests that END inside the
window. Once it has closed the clients stop sending, the pool drains, and a
sample of the finished requests, drawn from the seed with the longest in it,
is held against the plain reference's logits.

The list of requests (`pool` in the cell's file) has to outlast the clients'
whole life, from their start in set-up to the window's close: a client that
finds the list at its end goes home, the engine runs on with fewer lanes
loaded, and the rate read is the list's length over the window, whatever the
engine could do (PR 35: 1,259.85 tokens/s five times over, from an engine
that does 2,700). Such a run raises `BenchError` and prints no result.
"""
import threading
import time

import numpy as np

from benchmark import harness, traffic


class _Done:
    """One request as its client saw it end."""
    __slots__ = ("index", "t_end", "ttft", "tpot", "tokens", "error")

    def __init__(self, index):
        self.index = index
        self.t_end = self.ttft = self.tpot = self.tokens = self.error = None


class Driver:
    def __init__(self, cfg, workload, seed, reference):
        self.cfg, self.wl, self.seed = cfg, workload, int(seed)
        self.ref = reference
        self.done = []
        self.inflight = {}       # request index -> its future, while it runs
        self.tokens_ended = 0    # generated tokens of the requests that ended
        self.engine = None       # calibrate.py hands one engine to many seeds
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.next_index = 0
        self.t_clients = None    # when the clients started
        self.dry_at = None       # when a client first found the list empty

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                           GenerationPool)
        cfg = self.cfg
        self._draw_requests()
        dcfg = DecoderConfig(vocab_size=cfg["vocab_size"],
                             hidden=cfg["n_embd"], layers=cfg["n_layer"],
                             heads=cfg["n_head"],
                             max_seq_len=cfg["n_positions"],
                             mlp_ratio=cfg["mlp_ratio"])
        weights = self.ref.make_weights(cfg, self.seed)
        eng = cfg["engine"]
        from paddle_tpu.flags import get_flag
        block = int(get_flag("FLAGS_generation_block_size"))
        t0 = time.perf_counter()
        if self.engine is None:
            self.engine = GenerationEngine(
                dcfg, weights, decode_width=eng["decode_width"],
                num_blocks=eng["kv_pool_tokens"] // block)
            self.engine.warmup()
        else:
            self.engine.params = weights
        del weights
        harness.say("engine warm-up %.1fs; token_budget %d, prefill_chunk %d, "
                    "block_size %d, kernel %s"
                    % (time.perf_counter() - t0, self.engine.token_budget,
                       self.engine.prefill_chunk, self.engine.kv.block_size,
                       self.engine.kernel))
        self.pool = GenerationPool(self.engine)
        self._start_clients()

    def _draw_requests(self):
        t0 = time.perf_counter()
        self.requests = traffic.requests(self.wl, self.cfg, self.seed)
        harness.say("%d requests drawn in %.2fs"
                    % (len(self.requests), time.perf_counter() - t0))

    def _start_clients(self):
        """Start the closed loop and wait for its run-in: every lane occupied
        and the first `warm_completions` requests ended."""
        wl = self.wl
        self.threads = [threading.Thread(target=self._client, daemon=True,
                                         name="bench-client-%d" % i)
                        for i in range(wl["clients"])]
        self.t_clients = time.monotonic()
        for t in self.threads:
            t.start()
        want = wl["warm_completions"]
        t0 = time.perf_counter()
        while True:
            with self.lock:
                n = len(self.done)
            if n >= want:
                break
            if time.perf_counter() - t0 > 600:
                raise harness.BenchError("%d of %d warm-up requests completed "
                                         "in 600 s" % (n, want))
            time.sleep(0.002)   # the window opens on the completion itself
        harness.say("clients' run-in %.1fs: %d of %d requests ended"
                    % (time.perf_counter() - t0, n, len(self.requests)))

    def _client(self):
        from paddle_tpu.generation import GenerationRequest
        while not self.stop.is_set():
            with self.lock:
                i = self.next_index
                self.next_index += 1
            if i >= len(self.requests):
                if self.dry_at is None and not self.stop.is_set():
                    self.dry_at = time.monotonic()
                return
            prompt, new = self.requests[i]
            rec = _Done(i)
            fut = None
            try:
                with harness.span("client_request"):
                    fut = self.pool.submit(GenerationRequest(
                        prompt=prompt.tolist(), max_new_tokens=new,
                        eos_token=self.wl.get("eos")))
                    with self.lock:
                        self.inflight[i] = fut
                    res = fut.result(timeout=600)
                tr = fut.trace
                rec.tokens = list(res.tokens)
                if len(rec.tokens) != new or res.finish_reason != "length":
                    rec.error = "finished %r with %d of %d tokens" % (
                        res.finish_reason, len(rec.tokens), new)
                rec.ttft = tr.t_first_token - tr.t0
                if tr.tokens > 1:
                    rec.tpot = (tr.t_last_token - tr.t_first_token) \
                        / (tr.tokens - 1)
            except Exception as e:      # a failed request is a result
                rec.error = repr(e)
            rec.t_end = time.monotonic()
            with self.lock:
                if self.inflight.pop(i, None) is not None:
                    self.tokens_ended += fut.trace.tokens
                self.done.append(rec)

    # -- the timed part ---------------------------------------------------------
    def _pool_held(self):
        """Raise where a client has gone home for want of a request: from then
        on the run measured the list's length and not the engine."""
        dry_at = self.dry_at
        if dry_at is None:
            return
        self.release()      # let what is in flight finish, then say why
        raise harness.BenchError(
            "the pool ran dry: all %d requests of the cell's `pool` were sent "
            "%.1f s after the clients' start, %.1f s before this point; the "
            "clients no longer keep the engine loaded and the rate would be "
            "the list's, not the engine's. Raise `pool` in the workload file "
            "(a `benchmark` issue)" % (
                len(self.requests), dry_at - self.t_clients,
                time.monotonic() - dry_at))

    def steady(self, seconds):
        time.sleep(seconds)
        self._pool_held()

    def _counters(self):
        from paddle_tpu.monitor import stat_get, timer_get
        t = timer_get("TIMER_generation_mixed_step_us")
        return {"steps": t["count"], "step_us": t["sum"],
                "pad_tokens": stat_get("STAT_generation_pad_tokens"),
                "tokens": stat_get("STAT_generation_tokens")}

    def _tokens_out(self):
        """Generated tokens that have reached the host so far, over every
        request: those that ended, and those in flight as far as they are."""
        with self.lock:
            return self.tokens_ended + sum(f.trace.tokens for f in
                                           self.inflight.values())

    def window(self, seconds):
        c0 = self._counters()
        n0 = self._tokens_out()
        t0 = time.monotonic()
        time.sleep(seconds)
        t1 = time.monotonic()
        n1 = self._tokens_out()
        c1 = self._counters()
        self._pool_held()
        self.stop.set()
        with self.lock:
            self.sample = [r for r in self.done if t0 <= r.t_end < t1]
        dt = t1 - t0
        ok = [r for r in self.sample if r.error is None]
        failed = len(self.sample) - len(ok)

        def p90(vals):
            # a failed request lies beyond any percentile
            vals = sorted(vals) + [float("inf")] * failed
            return vals[min(len(vals) - 1, int(np.ceil(0.9 * len(vals))) - 1)]
        e2e = {"serve_out_tokens_per_s": (n1 - n0) / dt}
        if self.sample:
            # reported through metrics/ttft_p90_ms.py, per layer: in a loop
            # that keeps the engine saturated it is the wait for a prompt slot
            e2e["ttft_p90_ms"] = 1e3 * p90([r.ttft for r in ok])
            e2e["tpot_p90_ms"] = 1e3 * p90([r.tpot for r in ok
                                            if r.tpot is not None])
        ttfts = sorted(r.ttft for r in ok)
        if ttfts:
            harness.say("%d requests ended in the window; ttft s: median %.2f "
                        "p80 %.2f p90 %.2f max %.2f" % (
                            len(self.sample), ttfts[len(ttfts) // 2],
                            ttfts[int(0.8 * (len(ttfts) - 1))],
                            ttfts[int(0.9 * (len(ttfts) - 1))], ttfts[-1]))
        counters = {k: c1[k] - c0[k] for k in c0}
        if counters["steps"]:
            harness.say("%d tokens reached the host in the window; %d engine "
                        "steps of %.2f ms" % (
                            n1 - n0, counters["steps"],
                            counters["step_us"] / counters["steps"] / 1e3))
        counters["token_budget"] = self.engine.token_budget
        counters["finished"] = [(len(self.requests[r.index][0]),
                                 len(r.tokens)) for r in ok]
        for r in self.sample:
            if r.error is not None:
                harness.say("request %d failed: %s" % (r.index, r.error))
        return {"end_to_end": e2e, "window_s": dt,
                "attempted": len(self.sample), "failed": failed,
                "counters": counters}

    def drain(self):
        """Stop sending and let what is in flight finish."""
        self.stop.set()
        for t in self.threads:
            t.join(timeout=600)
        self.pool.close()
        self.pool = None

    def release(self):
        self.drain()
        self.engine = None

    # -- correct ------------------------------------------------------------------
    def compare(self):
        cfg, wl = self.cfg, self.wl
        ok = [r for r in self.sample if r.error is None]
        if not ok:
            return harness.held({n: float("nan") for n in self.ref.LIMITS},
                                self.ref)
        rng = np.random.RandomState((self.seed * 31 + 5) % (2 ** 32))
        longest = max(ok, key=lambda r: len(self.requests[r.index][0]))
        rest = [r for r in ok if r is not longest]
        k = min(len(rest), wl["check_requests"] - 1)
        picked = [longest] + [rest[i] for i in
                              rng.choice(len(rest), k, replace=False)]
        pmax = wl["prompt_len"]["max"]
        nmax = wl["new_tokens"]["max"]
        weights = self.ref.make_weights(cfg, self.seed)
        ref = self.ref.Reference(cfg, pad_to=pmax + nmax, new_tokens=nmax)
        t0 = time.perf_counter()
        gaps = [ref.gaps(weights, self.requests[r.index][0], r.tokens)
                for r in picked]
        self.gaps = gaps
        harness.say("reference: %d requests, %d served tokens in %.1fs; "
                    "prompts %s" % (
                        len(picked), sum(len(g) for g in gaps),
                        time.perf_counter() - t0,
                        [len(self.requests[r.index][0]) for r in picked]))
        return harness.held(self.ref.compare(gaps), self.ref)
