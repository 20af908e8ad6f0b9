"""Device time by the program's scopes, host time by the program's spans.

Beside `trace_reduce.py`, for the per-layer readers that follow one phase of
the program from PR to PR (the sampler, the paged attention, the optimizer):
they name a scope the program opened with `jax.named_scope`, or a host span
it opened with `telemetry.span`, and not an XLA instruction number.

Where a device operation's scope path comes from. The profiler names an
operation by its HLO instruction (`%fusion.35 = f32[...] fusion(...)`). The
path (`jit(step)/transpose(jvp(forward))/BertModel/Linear/dot_general`) is in
the `.xplane.pb` as well, but as the stat `tf_op` of the event's METADATA,
which `jax.profiler.ProfileData` (and so `trace_reduce.from_xplane`) does not
show: seen on the chip, PR 27. So `path_of` looks in three places, in order:
the event's own stats (`tf_op`; there once `from_xplane` keeps metadata
stats), `op_name="..."` inside the event's HLO text, and the program's own
table from instruction to path (`paddle_tpu.telemetry.device_op_names()`,
read from each compiled step's text), joined by the name of the module on the
`XLA Modules` line and the instruction's name. A program that keeps no such
table (the parent of PR 27) gives no paths, and every reader returns None.

Which components of a path are the program's. By the program's convention
(`paddle_tpu/telemetry.py`): lower-case words for phases, class names for
layers. Here: every component but the last (the primitive), less jax's own
(`jit(f)`, `while`, `body`, `closed_call`, a function's qualified name ...);
`jvp(x)`, `transpose(jvp(x))`, `vmap(x)` are unwrapped to `x`, and a path with
a `transpose(` in it is the backward pass.

Times are SELF times on the `XLA Ops` line as `trace_reduce._self_times`
reckons them, clipped to the benchmark's traced span as `trace_reduce.reduce`
clips, so the scopes' seconds add up to the device's busy seconds.
"""
import bisect
import re

from benchmark import trace_reduce

PROGRAM_MARK = "pt/"      # host spans the program opens (telemetry.span)
UNSCOPED = "unscoped"
BACKWARD = "~bwd"         # suffix of a scope's key in the backward pass
NOTE_ROWS = 40
UNSCOPED_ROWS, UNSCOPED_CHARS = 12, 200   # the list of what no scope names
# the step's program on the trace's `XLA Modules` line, by kind of cell
STEP_MODULE = {"train": "jit_step", "serve": "jit_generation_mixed"}

# jax's own words in a name stack: control flow and call wrappers
_JAX_WORDS = frozenset((
    "while", "body", "cond", "scan", "closed_call", "core_call", "checkpoint",
    "remat", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin", "pjit", "xla_call", "shard_map", "pmap", "named_call",
    "call_exported"))
_UNWRAP = frozenset(("jvp", "transpose", "vmap", "batch", "linearize"))
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
_SCOPE = re.compile(r"^[A-Za-z]\w*$")   # not `_take`, `_sample_one`: functions
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_BRANCH = re.compile(r"^branch_\d+_fun$")
_NUMBERED = re.compile(r"(%[A-Za-z_][\w\-]*?)(?:\.\d+)+\b")


def scopes_of(path):
    """-> ([the program's scope names, outermost first], backward?)."""
    parts = [p for p in path.rstrip(":").split("/") if p]
    backward = "transpose(" in path
    out = []
    for comp in parts[:-1]:           # the last component is the primitive
        while True:
            m = _WRAPPED.match(comp)
            if not m:
                break
            if m.group(1) not in _UNWRAP:
                comp = ""             # jit(f), pjit(f): a function of jax's
                break
            comp = m.group(2)
        if comp and _SCOPE.match(comp) and comp not in _JAX_WORDS \
                and not _BRANCH.match(comp):
            out.append(comp)
    return out, backward


def instruction_of(name):
    """`%fusion.35 = f32[...] fusion(...)` -> `fusion.35`."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def program_names():
    """The program's table {module: {instruction: path}}, or {} where the
    program keeps none."""
    try:
        from paddle_tpu import telemetry
        return telemetry.device_op_names()
    except Exception:
        return {}


def path_of(name, stats, table):
    """The scope path of one device event, or None."""
    p = stats.get("tf_op")
    if p:
        return str(p)
    m = _OP_NAME.search(name)
    if m:
        return m.group(1)
    if table:
        return table.get(instruction_of(name))
    return None


def _clip(events, lo, hi):
    out = []
    for name, s, d, st in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a, st])
    return out


def _window(planes, devs):
    w = trace_reduce.window_of(planes)
    if w is not None:
        return w
    evs = [e for pl in devs for e in trace_reduce.op_events(pl)[0]]
    if not evs:
        return 0.0, 0.0
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def module_of(name):
    """`jit_step(11786659159095024637)` -> `jit_step`."""
    return name.split("(", 1)[0]


def device_by_scope(planes, names=None):
    """-> None where the trace holds no device plane, else a dict:

    by_scope   {innermost scope (+ `~bwd` in the backward pass): seconds}
    rows       [(scopes, backward, seconds)] one per distinct path
    busy_s     the sum of all rows: the device's busy seconds
    named      whether any operation came with a path at all
    unscoped   {module and instruction, or a path with no scope of the
               program's in it: seconds}: what `unscoped` is made of
    modules    {module: {"runs": executions inside the slice, counted by the
               share of each that lies inside, "seconds": inside the slice}}

    Seconds are means over the device planes."""
    devs = trace_reduce.device_planes(planes)
    if not devs:
        return None
    if names is None:
        names = program_names()
    lo, hi = _window(planes, devs)
    rows, modules, named = {}, {}, False
    for pl in devs:
        mods = sorted((s, s + d, module_of(n)) for l in pl["lines"]
                      if l["name"] == "XLA Modules"
                      for n, s, d, _ in l["events"])
        starts = [m[0] for m in mods]
        full = {}
        for s, e, n in mods:
            full.setdefault(n, []).append(e - s)
            inside = min(e, hi) - max(s, lo)
            if inside > 0:
                rec = modules.setdefault(n, {"runs": 0.0, "seconds": 0.0})
                rec["seconds"] += inside / 1e9
        for n, durs in full.items():
            if n in modules and sum(durs) > 0:
                mean = sum(durs) / len(durs)
                modules[n]["runs"] = modules[n]["seconds"] * 1e9 / mean

        def module_at(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else ""
        _, lines = trace_reduce.op_events(pl)
        for l in lines:
            keyed = []
            for name, s, d, st in _clip(l["events"], lo, hi):
                mod = module_at(s)
                path = path_of(name, st, names.get(mod))
                # an operation with no path keeps its own name, after a NUL
                keyed.append([path or "\0" + mod + " " + name, s, d, st])
            for path, ns in trace_reduce._self_times(keyed).items():
                rows[path] = rows.get(path, 0.0) + ns
    n_dev = len(devs)
    for rec in modules.values():
        rec["runs"] /= n_dev
        rec["seconds"] /= n_dev
    out_rows, by_scope, unscoped = [], {}, {}
    for path, ns in rows.items():
        sec = ns / 1e9 / n_dev
        if path.startswith("\0"):
            scopes, backward = [], False
            unscoped[path[1:]] = sec
        else:
            scopes, backward = scopes_of(path)
            named = True
            if not scopes:
                unscoped[path] = unscoped.get(path, 0.0) + sec
        out_rows.append((scopes, backward, sec))
        key = (scopes[-1] + (BACKWARD if backward else "")) if scopes \
            else UNSCOPED
        by_scope[key] = by_scope.get(key, 0.0) + sec
    return {"by_scope": by_scope, "rows": out_rows, "named": named,
            "busy_s": sum(r[2] for r in out_rows), "modules": modules,
            "unscoped": unscoped}


def under(red, scope, backward=None):
    """Seconds under `scope` (anywhere in the path); `backward` True or False
    keeps that pass only."""
    return sum(sec for scopes, bwd, sec in red["rows"]
               if scope in scopes and (backward is None or bwd == backward))


def runs(red, module_prefix):
    """Executions of the step's program inside the slice."""
    return sum(m["runs"] for n, m in red["modules"].items()
               if n.startswith(module_prefix))


def host_by_span(planes):
    """The program's host spans inside the traced slice -> dict:

    total_s   {span: seconds, clipped to the slice}
    self_s    {span: seconds less what its children on the same thread cover}
    count     {span: how many, each counted by the share inside the slice}

    or None where the trace holds no `pt/` span."""
    lo_hi = trace_reduce.window_of(planes)
    total, self_s, count = {}, {}, {}
    for p in planes:
        if p["name"].startswith("/device:"):
            continue
        for l in p["lines"]:
            evs = [e for e in l["events"]
                   if e[0].startswith(PROGRAM_MARK) and e[2] > 0]
            if not evs:
                continue
            inside = _clip(evs, *lo_hi) if lo_hi else evs
            for n, ns in trace_reduce._self_times(inside).items():
                self_s[n] = self_s.get(n, 0.0) + ns / 1e9
            for n, _, d, _ in inside:
                total[n] = total.get(n, 0.0) + d / 1e9
            full = {}
            for n, _, d, _ in evs:
                full.setdefault(n, []).append(d)
            for n, durs in full.items():
                mean = sum(durs) / len(durs)
                got = sum(e[2] for e in inside if e[0] == n)
                count[n] = count.get(n, 0.0) + got / mean
    if not total:
        return None
    return {"total_s": total, "self_s": self_s, "count": count}


# --- what the readers share ------------------------------------------------

def _top(table):
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in rows[:NOTE_ROWS]]


def unscoped_kinds(unscoped):
    """[[module and instruction with the numbers XLA gave taken out, seconds,
    how many instructions]]: twelve copies of one shape, one a layer, read as
    one line."""
    kinds = {}
    for name, sec in unscoped.items():
        kind = _NUMBERED.sub(r"\1", name)[:UNSCOPED_CHARS]
        rec = kinds.setdefault(kind, [0.0, 0])
        rec[0] += sec
        rec[1] += 1
    rows = sorted(kinds.items(), key=lambda kv: -kv[1][0])
    return [[k, v[0], v[1]] for k, v in rows[:UNSCOPED_ROWS]]


def device(ctx):
    """The device reduction of this run, computed once and noted in the
    result (`notes.device_by_scope`: seconds by innermost scope). None where
    there is no device plane, or the program named nothing."""
    if "_device_by_scope" not in ctx:
        red = None
        if ctx.get("planes") is not None:
            red = device_by_scope(ctx["planes"])
        if red is not None and not red["named"]:
            red = None
        ctx["_device_by_scope"] = red
        if red is not None:
            ctx["notes"]["device_by_scope"] = {
                "busy_s": red["busy_s"], "seconds": _top(red["by_scope"]),
                "unscoped_ops": unscoped_kinds(red["unscoped"]),
                "modules": {n: [m["runs"], m["seconds"]]
                            for n, m in red["modules"].items()}}
    return ctx["_device_by_scope"]


def host(ctx):
    """The host reduction of this run, computed once and noted in the result
    (`notes.host_by_span`: self seconds and counts by span)."""
    if "_host_by_span" not in ctx:
        red = None
        if ctx.get("planes") is not None:
            red = host_by_span(ctx["planes"])
        ctx["_host_by_span"] = red
        if red is not None:
            ctx["notes"]["host_by_span"] = {
                "self_s": _top(red["self_s"]),
                "count": dict(red["count"])}
    return ctx["_host_by_span"]


def scope_ms_a_step(ctx, scopes, module_prefix, backward=None):
    """Device milliseconds under any of `scopes`, a step of the program whose
    module's name starts with `module_prefix`. None where the trace names
    nothing; a BenchError where it names operations but none under a scope
    asked for: a name lost in a refactoring fails loudly, it does not read
    0."""
    from benchmark.harness import BenchError
    red = device(ctx)
    if red is None:
        return None
    n = runs(red, module_prefix)
    if n <= 0:
        raise BenchError("the traced slice holds no execution of a module "
                         "named %s*: %s" % (module_prefix,
                                            sorted(red["modules"])))
    sec = 0.0
    for s in scopes:
        got = under(red, s, backward)
        if got <= 0:
            raise BenchError("the device trace names operations, but none "
                             "under the scope %r%s" % (
                                 s, "" if backward is None else
                                 " (backward=%s)" % backward))
        sec += got
    return 1e3 * sec / n
