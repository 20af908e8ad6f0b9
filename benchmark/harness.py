"""What every cell's run shares: finding the cell's files by name, the look
for the chip, the compile cache, the compile counter, the traced slice, the
per-layer readers and the result line. Nothing here knows a cell, a
configuration or a metric by name.

Layout, found by the names in BENCHMARK.json (`data_dirs` are searched in
order, so a later PR adds files and edits none):

    workloads/<cell>.json     the traffic mix: parameters only
    configs/<config>.json     sizes as run, `driver`, `reference`, precision
    drivers/<driver>.py       drives one entry point of the program
    references/<reference>.py plain reference, weights from the seed, LIMITS
    metrics/<x>.py            reader of the per-layer metric `x` or `x.<kind>`
"""
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """The run cannot stand for its cell: exit non-zero, print no result."""


def say(msg):
    print("[bench] %s" % msg, file=sys.stderr, flush=True)


# --- finding files by name ----------------------------------------------------

class Files:
    def __init__(self, spec_path=None, data_dirs=None):
        self.spec_path = spec_path or os.path.join(ROOT, "BENCHMARK.json")
        self.data_dirs = list(data_dirs or []) + [HERE]
        with open(self.spec_path) as f:
            self.spec = json.load(f)

    def find(self, kind, name, ext):
        for d in self.data_dirs:
            p = os.path.join(d, kind, name + ext)
            if os.path.isfile(p):
                return p
        raise BenchError("no %s/%s%s under %s" % (kind, name, ext,
                                                  self.data_dirs))

    def load_json(self, kind, name):
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def load_module(self, kind, name):
        path = self.find(kind, name, ".py")
        modname = "benchmark_%s_%s" % (kind, name)
        if modname in sys.modules:
            return sys.modules[modname]
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name):
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError("BENCHMARK.json names no workload %r" % name)

    def metrics_of(self, cell_name, group):
        """The metrics of `group` (end_to_end | per_layer) this cell reports:
        those that list it under `workloads`, or list nothing."""
        return [m for m in self.spec[group]
                if "workloads" not in m or cell_name in m["workloads"]]


# --- device, caches, counters -------------------------------------------------

def device_info(want_chips, rehearse):
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse:
        if info["platform"] != "tpu":
            raise BenchError("JAX found no accelerator (platform=%r): a "
                             "benchmark run proves nothing on a CPU"
                             % info["platform"])
        if info["count"] < want_chips:
            raise BenchError("the cell needs %d chip(s), JAX reports %d"
                             % (want_chips, info["count"]))
    return info


def peak_of(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise BenchError("no peaks on record for device_kind %r: add it to "
                         "benchmark/peaks.json with its source" % kind)
    return peaks[kind]


def compile_cache():
    """The program's own rule (`core/program_cache.py`): the directory
    JAX_COMPILATION_CACHE_DIR names where it is set, else the fixed
    `<checkout>/.paddle_tpu_cache/aot/xla`. Fixed, inside the checkout, so
    only a cell's first run there compiles."""
    import jax
    from paddle_tpu.core import program_cache
    d = program_cache.resolve_dir()
    if d is not None:
        program_cache.ensure_xla_cache(d)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """What jax itself reports (copied from `chip_smoke.CompileCounter`):
    executables built or fetched on a jit-cache miss, persistent-cache hits
    and writes."""

    _BUILD = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _WRITE = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as m
        self.builds = self.hits = self.writes = 0
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == self._BUILD:
            self.builds += 1

    def _on_event(self, event, **_kw):
        if event == self._HIT:
            self.hits += 1
        elif event == self._WRITE:
            self.writes += 1


def held(numbers, ref_mod):
    """-> ({name: value}, {name: limit}) of the numbers a cell is held to:
    every name in the reference module's LIMITS (each stated there beside the
    readings it was set from). What else the comparison read is printed as
    information."""
    missing = [n for n in ref_mod.LIMITS if n not in numbers]
    if missing:
        raise BenchError("the comparison gave no %s" % missing)
    say("read but not held: %s" % (" ".join(
        "%s=%.4g" % (k, v) for k, v in numbers.items()
        if k not in ref_mod.LIMITS) or "nothing"))
    return ({n: numbers[n] for n in ref_mod.LIMITS}, dict(ref_mod.LIMITS))


class MemoryPeak:
    """Peak device memory on the fullest chip. The TPU's allocator counts
    live buffers (`bytes_in_use`) apart from what loaded programs reserve for
    their temporaries (`bytes_reserved`: 11.7 GB of the BERT step's 13.6);
    the chip holds both at once. Their two PEAKS may fall at different
    instants, so the sum is SAMPLED from a thread, both counters read in one
    call, from set-up to the window's close; the allocator's own
    `peak_bytes_in_use` is a floor under it (a sample can miss a short
    peak of the buffers, never invent one). Nothing is clipped: a reading
    over the chip's `bytes_limit` is an error."""

    PERIOD_S = 0.1

    def __init__(self):
        import threading
        self.sampled = {}        # device index -> largest in_use + reserved
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-memory")
        self._thread.start()

    def _sample(self):
        import jax
        stats = []
        for i, d in enumerate(jax.local_devices()):
            st = d.memory_stats() or {}
            both = int(st.get("bytes_in_use", 0)) \
                + int(st.get("bytes_reserved", 0))
            self.sampled[i] = max(self.sampled.get(i, 0), both)
            stats.append(st)
        return stats

    def _loop(self):
        while not self._stop.wait(self.PERIOD_S):
            self._sample()

    def stop(self):
        self._stop.set()
        self._thread.join()

    def close(self):
        """Stop sampling; -> bytes on the fullest chip."""
        self.stop()
        peak = 0
        for i, st in enumerate(self._sample()):
            here = max(self.sampled[i], int(st.get("peak_bytes_in_use", 0)))
            say("memory of device %d: sampled peak of in_use + reserved %d; "
                "the allocator's %s" % (i, self.sampled[i],
                                        json.dumps(st, sort_keys=True)))
            if "bytes_limit" in st and here > int(st["bytes_limit"]):
                raise BenchError("memory peak %d over the chip's limit %d"
                                 % (here, st["bytes_limit"]))
            peak = max(peak, here)
        return peak


# --- the traced slice ---------------------------------------------------------

class Tracer:
    """A profiler trace of one short slice, kept in a fixed directory inside
    the checkout and removed once reduced."""

    def __init__(self, name):
        self.dir = os.path.join(ROOT, ".bench_trace", name)

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        from benchmark import trace_reduce
        jax.profiler.stop_trace()
        planes = trace_reduce.from_xplane(trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return planes


def span(name):
    """A host span of the benchmark's own in the profiler's trace; "traced"
    marks the traced slice (trace_reduce.window_of)."""
    import jax
    return jax.profiler.TraceAnnotation("bench/" + name)


# --- one run ------------------------------------------------------------------

def run_cell(files, cell_name, seed, seconds, trace, t_start, rehearse=False):
    """-> the result dict of one run (the caller prints it)."""
    cell = files.cell(cell_name)
    wl = files.load_json("workloads", cell_name)
    cfg = files.load_json("configs", cell["config"])
    info = device_info(cell["chips"], rehearse)
    peak = None if rehearse else peak_of(info["kind"])
    cache_dir = compile_cache()
    counter = CompileCounter()
    memory = MemoryPeak()
    say("cell %s seed %d on %s; compile cache %s; %.1fs since the process's "
        "first line" % (cell_name, seed, json.dumps(info), cache_dir or "off",
                        time.time() - t_start))
    driver_mod = files.load_module("drivers", cfg["driver"])
    ref_mod = files.load_module("references", cfg["reference"])
    drv = driver_mod.Driver(cfg=cfg, workload=wl, seed=seed, reference=ref_mod)
    try:
        drv.setup()
        setup_builds = counter.builds
        planes = None
        seconds = float(seconds)
        if trace:
            slice_s = min(float(wl.get("trace_seconds", 5.0)), seconds / 2)
            tracer = Tracer(cell_name)
            tracer.start()
            with span("traced"):
                drv.steady(slice_s)
            planes = tracer.stop()
            seconds -= slice_s
        setup_s = time.time() - t_start
        builds0 = counter.builds
        say("set-up %.1fs (%d executables built or fetched, %d cache hits, %d "
            "written); window %.1fs" % (setup_s, setup_builds, counter.hits,
                                        counter.writes, seconds))
        measured = drv.window(seconds)
        window_compiles = counter.builds - builds0
        mem_peak = memory.close()
    finally:
        memory.stop()
    drv.release()
    compared, limits = drv.compare()

    e2e = dict(measured["end_to_end"])
    e2e["setup_s"] = setup_s
    ctx = {"cell": cell, "config": cfg, "workload": wl, "device": info,
           "peak": peak, "counters": measured.get("counters", {}),
           "end_to_end": e2e, "window_s": measured["window_s"],
           "window_compiles": window_compiles, "memory_peak_bytes": mem_peak,
           "planes": planes, "trace": None, "notes": {}}
    device = dict(info, memory_peak_bytes=mem_peak)
    result = {"correct": None, "attempted": measured["attempted"],
              "failed": measured["failed"], "metrics": {}, "device": device}
    if trace:
        from benchmark import trace_reduce
        if rehearse and not trace_reduce.device_planes(planes):
            red = None      # a CPU rehearsal has no device plane to reduce
        else:
            red = trace_reduce.reduce(planes)
            if red["busy_s"] <= 0:
                raise BenchError("no operation ran on the device in the "
                                 "traced slice")
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        ctx["trace"] = red
        for m in files.metrics_of(cell_name, "per_layer"):
            base, _, kind = m["name"].partition(".")
            ctx["kind"] = kind
            value = files.load_module("metrics", base).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if ctx["notes"]:
            result["notes"] = ctx["notes"]
    else:
        for m in files.metrics_of(cell_name, "end_to_end"):
            if m["name"] not in e2e:
                raise BenchError("the driver measured no %r" % m["name"])
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    # every number compared, beside its limit: last on stderr and last in
    # the result line
    table = {n: {"value": compared[n], "limit": limits[n]}
             for n in compared}
    result["correct"] = bool(table) and measured["failed"] == 0 and all(
        v["value"] == v["value"] and v["value"] <= v["limit"]
        for v in table.values())
    result["compared"] = table
    for n, v in table.items():
        say("compared %-18s %.6g  limit %.6g  %s"
            % (n, v["value"], v["limit"],
               "ok" if v["value"] <= v["limit"] else "OVER"))
    say("correct=%s attempted=%d failed=%d" % (
        result["correct"], result["attempted"], result["failed"]))
    return result
