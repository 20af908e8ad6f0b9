"""The engine's serving thread on the profiler's trace, for the readers of
PR 40: its `pt/` spans and, inside them, the device runtime's own transfer
events; the device's idle intervals put against them.

The runtime records the host-to-device transfer of a host operand as
`DevicePut` on the calling thread, inside the compiled call's own event, where
`host_tracer_level` 2 keeps it (`harness.Tracer`). The program keeps the
compiled call's own transfer, so `pt/engine/dispatch` is read as the upload
(the time under those events) and the launch (the rest), with no change to
the program: the parent's trace reads the same.

The line is cut into pieces where an event opens or closes; each piece holds
the names open over it, outermost first. One thread's events nest or are
disjoint; a partial overlap is clipped to its parent, as
`trace_reduce._self_times` does.
"""
from benchmark import trace_reduce, trace_scopes

STEP = "pt/engine/step"
ENGINE = "pt/engine/"
TRANSFERS = frozenset(("DevicePut", "DevicePutWithSharding"))
UPLOAD = "DevicePut"        # the idle split's key for time under a transfer
NO_SPAN = "(no span)"


def engine_events(planes):
    """[(name, start, end)] of the host line with the most `pt/engine/step`
    spans: its `pt/` spans and transfers; [] where no line holds one."""
    lines = [l["events"] for p in planes
             if not p["name"].startswith("/device:") for l in p["lines"]]
    best = max(lines, default=[], key=lambda evs: sum(
        e[0] == STEP for e in evs))
    if not any(e[0] == STEP for e in best):
        return []
    return [(n, s, s + d) for n, s, d, _ in best if d > 0 and (
        n.startswith(trace_scopes.PROGRAM_MARK) or n in TRANSFERS)]


def cut(events):
    """[(start, end, path)], sorted and disjoint: time under no event gives
    no piece."""
    out, stack, t = [], [], 0.0      # stack: [name, end]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            end = stack[-1][1]
            out.append((t, end, tuple(n for n, _ in stack)))
            stack.pop()
            t = end
        if stack:
            e = min(e, stack[-1][1])
            out.append((t, s, tuple(n for n, _ in stack)))
        stack.append((name, e))
        t = s
    while stack:
        end = stack[-1][1]
        out.append((t, end, tuple(n for n, _ in stack)))
        stack.pop()
        t = end
    return [p for p in out if p[1] > p[0]]


def pieces(ctx):
    """The engine thread's pieces inside the traced slice, computed once a
    run; [] where the trace holds no engine step."""
    if "_engine_pieces" not in ctx:
        planes = ctx.get("planes") or []
        got = cut(engine_events(planes))
        window = trace_reduce.window_of(planes)
        if window is not None:
            lo, hi = window
            got = [(max(a, lo), min(b, hi), p) for a, b, p in got
                   if min(b, hi) > max(a, lo)]
        ctx["_engine_pieces"] = got
    return ctx["_engine_pieces"]


def is_upload(path):
    """A transfer inside one of the engine's spans."""
    return path[0].startswith(ENGINE) and any(n in TRANSFERS for n in path)


def seconds(got, pred):
    return sum(b - a for a, b, p in got if pred(p)) / 1e9


def key(path):
    """Where the idle split puts a piece: the innermost `pt/` span, or
    UPLOAD under a transfer inside one, or NO_SPAN."""
    spans = [n for n in path if n.startswith(trace_scopes.PROGRAM_MARK)]
    if not spans:
        return NO_SPAN
    return UPLOAD if path[-1] in TRANSFERS else spans[-1]


def idle_intervals(planes):
    """[(start, end)] of device 0's idle time inside the slice, as
    `trace_reduce.reduce` reckons it: the complement of the union of its
    operations' intervals."""
    pl = trace_reduce.device_planes(planes)[0]
    evs, lines = trace_reduce.op_events(pl)
    window = trace_reduce.window_of(planes)
    if window is None:
        window = (min((e[1] for e in evs), default=0.0),
                  max((e[1] + e[2] for e in evs), default=0.0))
    lo, hi = window
    merged = trace_reduce._union(
        [(max(s, lo), min(s + d, hi)) for l in lines
         for _, s, d, _ in l["events"]])
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def split(idle, got):
    """{key: idle ns}: each idle interval by its overlap with the pieces
    (both sorted and disjoint); what no piece covers goes to NO_SPAN."""
    out, j = {}, 0
    for a, b in idle:
        covered = 0.0
        while j < len(got) and got[j][1] <= a:
            j += 1
        k = j
        while k < len(got) and got[k][0] < b:
            s, e, path = got[k]
            o = min(e, b) - max(s, a)
            if o > 0:
                n = key(path)
                out[n] = out.get(n, 0.0) + o
                covered += o
            k += 1
        out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (b - a - covered)
    return out
