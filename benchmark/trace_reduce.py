"""From a profiler trace to numbers: busy time, idle gaps, time by operation.

The reduction works on a plain structure, so that it can be checked on a
small recorded trace (`tests/data/trace_small.json`):

    [{"name": plane, "lines": [{"name": line,
                                "events": [[name, start_ns, dur_ns, stats]]}]}]

`from_xplane` turns an `.xplane.pb` (read with `jax.profiler.ProfileData`,
nothing but JAX) into that structure.

Busy time of a device is the UNION of the intervals in which an operation
ran on it: overlapping and nested events (an XLA `while` holds its body's
operations; a fusion may overlap a copy) are not added twice. Time by
operation is SELF time: an event's duration less the part its children on
the same line cover, so a loop does not count its body again.
"""
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
# lines of a device plane that list single operations; where a plane has none
# of these, every line but SKIP_LINES counts
OP_LINES = ("XLA Ops",)
SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code", "Host Offload Ops")
HOST_MARK = "bench/"       # spans the benchmark's own files put on the host
NAME_CHARS = 160           # a TPU op event is named by its whole HLO text


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return hits[-1]


def from_xplane(path, keep_stats=("long_name", "tf_op", "hlo_category",
                                  "flops", "bytes_accessed", "hlo_op",
                                  "hlo_module", "name")):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for pl in data.planes:
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                st = {}
                for k, v in e.stats:
                    if keep_stats is None or k in keep_stats:
                        st[k] = v
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            st])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """name -> self nanoseconds over one line's events (properly nested or
    disjoint, as one hardware queue's are; partial overlaps are clipped)."""
    out = {}
    stack = []   # [name, end, child_ns, dur]
    for name, s, d, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and stack[-1][1] <= s:
            n, _, child, dur = stack.pop()
            out[n] = out.get(n, 0.0) + max(0.0, dur - child)
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    while stack:
        n, _, child, dur = stack.pop()
        out[n] = out.get(n, 0.0) + max(0.0, dur - child)
    return out


def device_planes(planes):
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]


def op_events(plane):
    """The single-operation events of one device plane."""
    named = [l for l in plane["lines"] if l["name"] in OP_LINES]
    lines = named or [l for l in plane["lines"]
                      if l["name"] not in SKIP_LINES]
    return [ev for l in lines for ev in l["events"]], lines


def host_spans(planes):
    """Every host event, [(name, start, end)], device planes left out."""
    out = []
    for p in planes:
        if p["name"].startswith("/device:") or p["name"] == \
                "Task Environment":
            continue
        for l in p["lines"]:
            for name, s, d, _ in l["events"]:
                if d > 0:
                    out.append((name, s, s + d))
    return out


def window_of(planes, mark=HOST_MARK + "traced"):
    """[start, end) of the benchmark's own span around the traced slice, or
    None where the trace holds none."""
    spans = [(s, e) for n, s, e in host_spans(planes) if n == mark]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


ATTRIBUTED_GAPS = 400    # the longest gaps are named; the rest are lumped


def _attribute(gaps, spans):
    """{what the host was doing: idle nanoseconds}. A gap goes to the
    innermost (shortest) host span that covers its middle; the benchmark's
    own spans win a tie."""
    import numpy as np
    out = {}
    gaps = sorted(gaps, reverse=True)
    rest = sum(g[0] for g in gaps[ATTRIBUTED_GAPS:])
    if rest:
        out["gaps_beyond_the_%d_longest" % ATTRIBUTED_GAPS] = rest
    if not spans:
        spans = [("host:no_span", 0.0, 0.0)]
    starts = np.array([s for _, s, _ in spans])
    ends = np.array([e for _, _, e in spans])
    rank = (ends - starts) + np.array(
        [0.0 if n.startswith(HOST_MARK) else 0.5 for n, _, _ in spans])
    for dur, mid in gaps[:ATTRIBUTED_GAPS]:
        cover = np.nonzero((starts <= mid) & (mid < ends))[0]
        what = spans[cover[np.argmin(rank[cover])]][0] if len(cover) \
            else "host:no_span"
        out[what] = out.get(what, 0.0) + dur
    return out


def reduce(planes, top=10, window=None):
    """-> dict(busy_s, window_s, devices, device_ops, idle_gaps).

    busy_s is averaged over the device planes; window_s is the benchmark's
    traced span where the trace holds one (or `window`), else first to last
    device event. Events are clipped to the window. device_ops: [[name,
    seconds]] by self time, summed over devices; idle_gaps: the longest gaps
    of device 0 as [[what the host was doing, seconds]]."""
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no %s* plane: %r"
                         % (DEVICE_PREFIX, [p["name"] for p in planes]))
    window = window or window_of(planes)
    per_dev, ops_total, gaps0 = [], {}, []
    spans = host_spans(planes)
    for i, pl in enumerate(devs):
        evs, lines = op_events(pl)
        if window is None:
            lo = min(e[1] for e in evs) if evs else 0.0
            hi = max(e[1] + e[2] for e in evs) if evs else 0.0
        else:
            lo, hi = window
        intervals, n_events = [], 0
        for l in lines:
            clipped = []
            for name, s, d, st in l["events"]:
                a, b = max(s, lo), min(s + d, hi)
                if b > a:
                    clipped.append([name, a, b - a, st])
            intervals += [(s, s + d) for _, s, d, _ in clipped]
            n_events += len(clipped)
            for n, ns in _self_times(clipped).items():
                ops_total[n] = ops_total.get(n, 0.0) + ns
        merged = _union(intervals)
        busy = sum(e - s for s, e in merged)
        per_dev.append({"plane": pl["name"], "busy_s": busy / 1e9,
                        "events": n_events})
        if i == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps0.append((b - a, (a + b) / 2))
    by_host = _attribute(gaps0, spans)
    return {
        "busy_s": sum(d["busy_s"] for d in per_dev) / len(per_dev),
        "window_s": (hi - lo) / 1e9,
        "devices": per_dev,
        "device_ops": [[n[:NAME_CHARS], ns / 1e9] for n, ns in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, ns / 1e9] for n, ns in
                      sorted(by_host.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": max([g[0] for g in gaps0], default=0.0) / 1e9,
    }


def events_matching(planes, pred):
    """Device op events [name, start, dur, stats] for which pred(name, stats)
    holds, over all device planes."""
    out = []
    for pl in device_planes(planes):
        evs, _ = op_events(pl)
        out.extend(e for e in evs if pred(e[0], e[3]))
    return out
