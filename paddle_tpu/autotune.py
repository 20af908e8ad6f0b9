"""Adaptive kernel dispatch: auto-tuned step geometry + kernel form.

The serving hot path has a real search space — kernel form
(reference | pallas), mixed-step geometry (block_size x prefill_chunk
x token_budget), the predictor's pad-to-bucket vs exact-shape choice —
but until ISSUE 16 every knob was a hand-set global flag, so one
geometry served every workload shape. The Ragged Paged Attention paper
(PAPERS.md) shows this geometry space is worth searching per shape;
this module is the searcher. Once per (shape-bucket, backend,
quant-mode) KEY it:

1. enumerates candidate forms (bounded by FLAGS_autotune_candidates;
   the reference/default form is always candidate #1, the Pallas
   kernel form is ordered last so small budgets search geometry only),
2. builds a throwaway trial engine per candidate (all alive for the
   duration of the tune — the candidate budget bounds the transient
   pool memory), then measures INTERLEAVED passes of a small
   deterministic probe workload (FLAGS_autotune_probe_tokens) so
   machine drift cannot systematically favor any candidate,
3. keeps only candidates whose token streams are BITWISE-IDENTICAL to
   the reference form's (keyed by request_id) — the eligibility gate
   that makes tuning safe to ship: a form that changes a single token
   can never win, and
4. picks the winner by measured time per generated token, installing
   it in the in-memory DispatchPolicy table and persisting it in the
   program cache's policy/ sidecar (core/program_cache.py:
   version-stamped, atomic-replace, self-healing on corruption).

Steady state afterwards is ONE dict lookup (DispatchPolicy.resolve —
the same disciplne as tracing/failpoints/slo); a restarted process
reloads the persisted winner and recompiles nothing, because the
resolved form rides the engine's program fingerprint meta
(generation/engine.py v=4) and the AOT trace entries were written when
the winner was first compiled.

Override precedence (docs/autotune.md, MIGRATION.md): explicitly-set
flags / ctor args PIN a knob (the policy searches only the free
dimensions) > persisted policy > flag defaults. With FLAGS_autotune
off (default) nothing here runs and the legacy flags behave exactly
as before.

Faults: every candidate trial passes the `autotune.measure` failpoint
(failpoints.py). A fault during a non-reference trial discards that
candidate (STAT_autotune_fallbacks); a fault during the reference
trial aborts the whole tune — the caller falls back to the reference/
default form and NOTHING is persisted, so the policy cache is never
poisoned by a half-measured search.

Instruments (docs/observability.md): STAT_autotune_trials / _wins /
_cache_hits / _fallbacks, TIMER_autotune_trial_us; the engine
publishes GAUGE_autotune_active / _step_time_us / _trials for its
resolved entry (retracted by the scheduler's _reset_engine).
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import numpy as np

from .failpoints import failpoint
from .flags import get_flag
from .kernels.paged_attention import resolved_form
from .monitor import stat_add, timer_observe

__all__ = ["CandidateForm", "DispatchPolicy", "generation_candidates",
           "key_for", "policies", "policy", "probe_requests", "reset",
           "resolve_generation", "tune_two_forms"]

# interleaved measurement passes per candidate: each pass serves a
# FRESH probe workload (seed varies per pass, so every pass measures
# cold prefill — see _probe_pass), the best (min) of all passes is the
# recorded time — small because trials run at engine construction
_TRIAL_PASSES = 3


class CandidateForm(NamedTuple):
    """One point of the generation search space. token_budget keeps
    the flag's semantics (0 = auto: decode_width*(1+spec) + chunk), so
    a persisted winner composes with any decode_width at apply time."""
    kernel: str
    block_size: int
    prefill_chunk: int
    token_budget: int

    @property
    def label(self) -> str:
        return "%s/bs%d/pc%d/tb%d" % self

    def as_entry(self) -> Dict[str, Any]:
        return {"kernel": self.kernel, "block_size": self.block_size,
                "prefill_chunk": self.prefill_chunk,
                "token_budget": self.token_budget, "label": self.label}


class DispatchPolicy:
    """The per-process policy table. resolve() is the steady-state hot
    path and is ONE dict lookup — no disk, no flags, no fallback logic
    (pinned by tests/test_autotune.py, same contract as the disarmed
    failpoint / tracing-off paths)."""

    def __init__(self) -> None:
        self._table: Dict[str, Dict[str, Any]] = {}

    def resolve(self, key: str) -> Optional[Dict[str, Any]]:
        return self._table.get(key)

    def install(self, key: str, entry: Dict[str, Any]) -> None:
        self._table[key] = dict(entry)

    def reset(self) -> None:
        self._table.clear()

    def snapshot(self) -> List[Dict[str, Any]]:
        """Compact per-key view for /statusz: key coordinates + the
        winning form + its measurement (full candidate tables stay in
        the entries / policy files)."""
        out = []
        for k in sorted(self._table):
            e = self._table[k]
            try:
                km = json.loads(k)
            except ValueError:
                km = {}
            out.append({
                "kind": km.get("kind"),
                "backend": km.get("backend"),
                "qm": km.get("qm"),
                "kvq": km.get("kvq"),
                "width": km.get("width"),
                "rows": km.get("rows"),
                "bucket": km.get("bucket"),
                "form": e.get("label"),
                "step_time_us": e.get("step_time_us"),
                "us_per_token": e.get("us_per_token"),
                "trials": e.get("trials"),
                "source": e.get("source", "tuned"),
            })
        return out


_POLICY = DispatchPolicy()


def policy() -> DispatchPolicy:
    return _POLICY


def policies() -> List[Dict[str, Any]]:
    """The /statusz autotune section's policy list."""
    return _POLICY.snapshot()


def reset() -> None:
    """Clear the in-memory table (tests / restart simulation). Policy
    files on disk are untouched — the next resolve re-loads them."""
    _POLICY.reset()


def key_for(key_meta: Dict[str, Any]) -> str:
    """Canonical policy-table key for a key-meta dict. Stable across
    processes (sorted JSON) so the same meta that fingerprints the
    disk entry also keys the in-memory table."""
    return json.dumps(key_meta, sort_keys=True, default=str)


def _lookup(key_meta: Dict[str, Any], program_cache_dir: Optional[str]):
    """memory -> disk lookup. Returns (key, entry_or_None, cache_dir,
    fingerprint); counts STAT_autotune_cache_hits on either hit and
    installs disk hits in memory so the hot path never touches disk
    again."""
    from .core import program_cache
    key = key_for(key_meta)
    entry = _POLICY.resolve(key)
    if entry is not None:
        stat_add("STAT_autotune_cache_hits")
        return key, entry, None, None
    cache_dir = program_cache.resolve_dir(program_cache_dir)
    fp = None
    if cache_dir is not None:
        fp = program_cache.policy_fingerprint(key_meta)
        entry = program_cache.load_policy(cache_dir, fp)
        if entry is not None:
            stat_add("STAT_autotune_cache_hits")
            _POLICY.install(key, dict(entry, source="disk"))
            entry = _POLICY.resolve(key)
    return key, entry, cache_dir, fp


def _publish(key: str, entry: Dict[str, Any], cache_dir: Optional[str],
             fp: Optional[str]) -> Dict[str, Any]:
    from .core import program_cache
    stat_add("STAT_autotune_wins")
    _POLICY.install(key, entry)
    if cache_dir is not None and fp is not None:
        program_cache.store_policy(cache_dir, fp, entry)
    return entry


# ---------------------------------------------------------------------------
# generation: candidate space + trial harness
# ---------------------------------------------------------------------------

def generation_candidates(defaults: CandidateForm,
                          pins: Dict[str, Any],
                          budget: int) -> List[CandidateForm]:
    """Deterministic candidate list, reference/default form FIRST,
    truncated to `budget`. Pinned knobs (explicit flags / ctor args)
    never vary. Geometry variants precede the kernel-form flip so a
    small budget searches geometry only — the Pallas form is the most
    expensive trial off-TPU (interpret mode) and the least likely CPU
    winner; TPU deployments raise FLAGS_autotune_candidates."""
    d = defaults
    out = [d]
    variants: List[CandidateForm] = []
    if "prefill_chunk" not in pins:
        variants += [d._replace(prefill_chunk=d.prefill_chunk * 4),
                     d._replace(prefill_chunk=d.prefill_chunk * 2),
                     d._replace(prefill_chunk=max(1, d.prefill_chunk // 2))]
    if "block_size" not in pins:
        variants += [d._replace(block_size=d.block_size * 2),
                     d._replace(block_size=max(1, d.block_size // 2))]
    if "kernel" not in pins:
        variants.append(d._replace(
            kernel="pallas" if d.kernel == "reference" else "reference"))
    for v in variants:
        if len(out) >= budget:
            break
        if v not in out:
            out.append(v)
    return out[:max(1, budget)]


def probe_requests(cfg, decode_width: int, probe_tokens: int,
                   seed: int = 20160829) -> list:
    """The deterministic trial workload: a handful of requests with a
    prompt-length spread (short chat turn .. long document) sharing
    `probe_tokens` generated tokens between them. Same seed every
    call, so every candidate form decodes the same problem and the
    bitwise eligibility gate compares like with like."""
    from .generation.engine import GenerationRequest
    from .generation.sampling import SamplingParams
    rng = np.random.default_rng(seed)
    n = max(2, min(int(decode_width), 4))
    msl = int(cfg.max_seq_len)
    new = max(2, int(probe_tokens) // n)
    spread = (2, msl // 4, msl // 2, (3 * msl) // 4)
    reqs = []
    for i in range(n):
        plen = max(1, min(msl - new - 1, spread[i % len(spread)]))
        prompt = [int(t) for t in
                  rng.integers(0, cfg.vocab_size, size=plen)]
        reqs.append(GenerationRequest(
            prompt=prompt, max_new_tokens=new,
            sampling=SamplingParams(temperature=0.7, top_k=5,
                                    seed=1000 + i),
            request_id="probe%d" % i))
    return reqs


def _build_trial_engine(cand: CandidateForm, cfg, params,
                        engine_kwargs: Dict[str, Any]):
    """Build + warm one candidate's throwaway trial engine. The
    autotune.measure failpoint fires here, once per candidate — a
    fault (or an invalid-candidate ctor error) discards the candidate
    before anything is measured."""
    from .generation.engine import GenerationEngine
    failpoint("autotune.measure")
    eng = GenerationEngine(cfg, params, autotune=False,
                           kernel=cand.kernel,
                           block_size=cand.block_size,
                           prefill_chunk=cand.prefill_chunk,
                           token_budget=cand.token_budget,
                           **engine_kwargs)
    eng.warmup()
    return eng


def _probe_pass(eng, cfg, probe_tokens: int, seed: int):
    """Drain one probe workload on a warm trial engine. Returns
    (seconds_per_token, seconds_per_step, streams) with streams keyed
    by request_id. Raises on nonconvergence. The caller varies `seed`
    per pass: identical prompts would hit the engine's own prefix
    cache from pass 2 on, and a probe measuring the cache-hit regime
    is blind to the chunked-prefill geometry it exists to search."""
    reqs = probe_requests(cfg, eng.decode_width, probe_tokens,
                          seed=seed)
    limit = (2 * cfg.max_seq_len + 4) * max(1, len(reqs))
    for r in reqs:
        eng.submit(r)
    results, steps = [], 0
    t0 = time.perf_counter()
    while not eng.idle and steps < limit:
        results.extend(eng.step())
        steps += 1
    dt = time.perf_counter() - t0
    if not eng.idle:
        raise RuntimeError("trial did not converge in %d steps" % limit)
    streams = {r.request_id: tuple(r.tokens) for r in results}
    tokens = sum(len(v) for v in streams.values())
    return dt / max(1, tokens), dt / max(1, steps), streams


def resolve_generation(cfg, params, *, num_blocks: int,
                       decode_width: int, spec_tokens: int,
                       quant_mode: str, kv_dtype: str, draft_kind: str,
                       draft_cfg=None, draft_params=None,
                       prefix_cache=None,
                       program_cache_dir: Optional[str] = None,
                       pins: Optional[Dict[str, Any]] = None
                       ) -> Optional[Dict[str, Any]]:
    """The generation engine's dispatch resolve: memory -> disk ->
    tune. Returns the policy entry (kernel + geometry + measurement)
    or None when tuning could not complete (reference trial fault) —
    the engine then runs the reference/default form and nothing is
    persisted."""
    import jax
    pins = dict(pins or {})
    key_meta = {
        "kind": "generation",
        "model": cfg.meta(),
        "width": int(decode_width),
        "spec": int(spec_tokens),
        "draft": str(draft_kind) if spec_tokens else "",
        "qm": str(quant_mode),
        "kvq": str(kv_dtype),
        "blocks": int(num_blocks),
        "backend": jax.default_backend(),
        "pins": {k: pins[k] for k in sorted(pins)},
    }
    key, entry, cache_dir, fp = _lookup(key_meta, program_cache_dir)
    if entry is not None:
        return entry

    budget = max(1, int(get_flag("FLAGS_autotune_candidates")))
    probe_tokens = max(4, int(get_flag("FLAGS_autotune_probe_tokens")))
    defaults = CandidateForm(
        kernel=str(pins.get("kernel", resolved_form())),
        block_size=int(pins.get("block_size",
                                get_flag("FLAGS_generation_block_size"))),
        prefill_chunk=int(pins.get(
            "prefill_chunk", get_flag("FLAGS_generation_prefill_chunk"))),
        token_budget=int(pins.get(
            "token_budget", get_flag("FLAGS_generation_token_budget"))))
    cands = generation_candidates(defaults, pins, budget)
    engine_kwargs = dict(num_blocks=num_blocks,
                         decode_width=decode_width,
                         spec_tokens=spec_tokens,
                         quant_mode=quant_mode, kv_dtype=kv_dtype,
                         draft=draft_kind, draft_cfg=draft_cfg,
                         draft_params=draft_params,
                         prefix_cache=prefix_cache,
                         program_cache_dir=program_cache_dir)

    # Phase 1 — build + warm every candidate's trial engine. A ctor
    # error / injected fault discards the candidate here; the
    # reference candidate aborts the whole tune (nothing persisted —
    # the cache is never poisoned by a half-measured search).
    t_tune = time.perf_counter()
    bad: Dict[int, Dict[str, Any]] = {}
    built: List[tuple] = []          # (i, cand, eng, elapsed_s)
    for i, cand in enumerate(cands):
        stat_add("STAT_autotune_trials")
        t0 = time.perf_counter()
        try:
            eng = _build_trial_engine(cand, cfg, params, engine_kwargs)
        except Exception as e:
            timer_observe("TIMER_autotune_trial_us",
                          (time.perf_counter() - t0) * 1e6)
            stat_add("STAT_autotune_fallbacks")
            if i == 0:
                return None
            bad[i] = dict(cand.as_entry(), eligible=False,
                          error=repr(e)[:160])
            continue
        built.append([i, cand, eng, time.perf_counter() - t0])

    # Phase 2 — INTERLEAVED measurement passes: every candidate
    # samples every machine-drift window, so process warmup / CPU
    # frequency drift cannot systematically favor later candidates
    # (the same honest-margin discipline as bench.py's best-of-N
    # blocks; a sequential probe measurably mis-picks under drift).
    # Each pass uses a fresh probe seed: repeated prompts would hit
    # the trial engines' prefix caches and measure the cache-hit
    # regime instead of the chunked-prefill geometry under search.
    meas: Dict[int, Dict[str, Any]] = {}
    for p in range(_TRIAL_PASSES):
        for rec in built:
            i, cand = rec[0], rec[1]
            if i in bad:
                continue
            t0 = time.perf_counter()
            try:
                s_tok, s_step, streams = _probe_pass(
                    rec[2], cfg, probe_tokens, seed=20160829 + p)
            except Exception as e:
                rec[3] += time.perf_counter() - t0
                stat_add("STAT_autotune_fallbacks")
                if i == 0:
                    # the reference form has no working measurement:
                    # no oracle, no winner, nothing persisted
                    return None
                bad[i] = dict(cand.as_entry(), eligible=False,
                              error=repr(e)[:160])
                meas.pop(i, None)
                continue
            rec[3] += time.perf_counter() - t0
            m = meas.setdefault(i, {"s_tok": s_tok, "s_step": s_step,
                                    "streams": {}})
            m["streams"][p] = streams
            if s_tok < m["s_tok"]:
                m["s_tok"], m["s_step"] = s_tok, s_step

    records: List[Dict[str, Any]] = []
    ref_streams = meas[0]["streams"]
    for i, cand in enumerate(cands):
        if i in bad:
            records.append(bad[i])
            continue
        m = meas.get(i)
        if m is None:          # built but never measured (passes == 0)
            continue
        # bitwise eligibility: EVERY pass's streams must match the
        # reference form's streams for the same probe workload
        eligible = m["streams"] == ref_streams
        if i and not eligible:
            stat_add("STAT_autotune_fallbacks")
        records.append(dict(cand.as_entry(), eligible=eligible,
                            us_per_token=round(m["s_tok"] * 1e6, 2),
                            step_time_us=round(m["s_step"] * 1e6, 2)))
    for rec in built:
        timer_observe("TIMER_autotune_trial_us", rec[3] * 1e6)

    eligible_recs = [r for r in records if r.get("eligible")]
    if not eligible_recs:  # cannot happen unless records is empty
        return None
    win = min(eligible_recs, key=lambda r: r["us_per_token"])
    entry = {
        "kernel": win["kernel"], "block_size": win["block_size"],
        "prefill_chunk": win["prefill_chunk"],
        "token_budget": win["token_budget"], "label": win["label"],
        "us_per_token": win["us_per_token"],
        "step_time_us": win["step_time_us"],
        "trials": len(records),
        "candidates": records,
        "tuned_s": round(time.perf_counter() - t_tune, 3),
        "source": "tuned",
    }
    return _publish(key, entry, cache_dir, fp)


# ---------------------------------------------------------------------------
# generic named-form tuner (the Predictor's bucket dispatch)
# ---------------------------------------------------------------------------

def tune_two_forms(key_meta: Dict[str, Any], *,
                   program_cache_dir: Optional[str],
                   forms: Dict[str, Callable[[], Any]],
                   reference: str,
                   compare: Callable[[Any, Any], bool],
                   passes: int = 3) -> Optional[Dict[str, Any]]:
    """Tune among named zero-arg forms (each runs the SAME work one
    way and returns its value): interleaved passes, winner = the
    eligible form with the best single-pass time, eligibility =
    compare(reference_value, value). Installs + persists the winner
    keyed by `key_meta`. A fault (autotune.measure) on the reference
    form aborts (returns None, nothing persisted); on another form,
    discards that form. Used by the Predictor's pad-to-bucket vs
    exact-shape dispatch (inference.py)."""
    key, entry, cache_dir, fp = _lookup(key_meta, program_cache_dir)
    if entry is not None:
        return entry
    order = [reference] + [n for n in forms if n != reference]
    best: Dict[str, float] = {}
    values: Dict[str, Any] = {}
    failed: set = set()
    for _ in range(max(1, passes)):
        for name in order:
            if name in failed:
                continue
            stat_add("STAT_autotune_trials")
            t0 = time.perf_counter()
            try:
                failpoint("autotune.measure")
                val = forms[name]()
            except Exception:
                timer_observe("TIMER_autotune_trial_us",
                              (time.perf_counter() - t0) * 1e6)
                stat_add("STAT_autotune_fallbacks")
                if name == reference:
                    return None
                failed.add(name)
                continue
            dt = time.perf_counter() - t0
            timer_observe("TIMER_autotune_trial_us", dt * 1e6)
            if name not in best or dt < best[name]:
                best[name] = dt
            values.setdefault(name, val)
    if reference not in best:
        return None
    eligible = {}
    for name, dt in best.items():
        ok = name == reference or compare(values[reference],
                                          values[name])
        if not ok:
            stat_add("STAT_autotune_fallbacks")
            continue
        eligible[name] = dt
    win = min(eligible, key=eligible.get)
    n_trials = sum(1 for n in order if n not in failed) * max(1, passes)
    entry = {
        "form": win, "label": win,
        "step_time_us": round(eligible[win] * 1e6, 2),
        "trials": n_trials,
        "candidates": [{"label": n,
                        "step_time_us": round(best[n] * 1e6, 2),
                        "eligible": n in eligible}
                       for n in order if n in best],
        "source": "tuned",
    }
    return _publish(key, entry, cache_dir, fp)
