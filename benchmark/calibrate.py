"""Margins of the comparison that decides `correct`, read on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3

Not part of a benchmark run. For one cell it drives the timed path's
comparison alone over many seeds in ONE process, beside the lower-precision
controls and the planted faults, and writes every reading to
`chiprun_out/calibrate_<cell>.json`: the lower and upper readings each limit
in `references/*.py` is set from (PERF.md section 6).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def _seeds(n, base):
    return [base + 7919 * i * i + 104729 * i for i in range(n)]


def calibrate_train(cfg, wl, ref_mod, drv_mod, args, out):
    lr = cfg["optimizer"]["learning_rate"]
    rows = wl["reference_rows_per_block"]
    ref = ref_mod.Reference(cfg, lr, rows)
    stand_ins = {"control_" + p: ref_mod.Reference(cfg, lr, rows, precision=p)
                 for p in ref_mod.CONTROLS}
    stand_ins.update({"fault_" + f: ref_mod.Reference(cfg, lr, rows, fault=f)
                      for f in ref_mod.FAULTS})
    B = wl["batch"]
    half = [2.0] * (B // 2) + [0.0] * (B - B // 2)   # mean over the kept half
    for i, seed in enumerate(_seeds(args.seeds, args.base)):
        rec = {"seed": seed, "runs": {}}
        if args.no_program:
            batch = drv_mod.traffic.mlm_batch(wl, cfg, seed)
        else:
            drv = drv_mod.Driver(cfg=cfg, workload=wl, seed=seed,
                                 reference=ref_mod)
            drv.setup()
            batch, rec["runs"]["program"] = drv.batch_np, drv.program
            drv.release()
        weights = ref_mod.make_weights(cfg, seed)
        t0 = time.perf_counter()
        base = ref.run(weights, batch, seed)
        rec["reference_s"] = time.perf_counter() - t0
        if i < args.controls:
            for name, r in stand_ins.items():
                rec["runs"][name] = r.run(weights, batch, seed)
            rec["runs"]["reference_other_masks"] = ref.run(weights, batch,
                                                           seed + 1)
            rec["runs"]["fault_half_batch"] = ref.run(weights, batch, seed,
                                                      row_weights=half)
        rec["compared"] = {k: ref_mod.compare(v, base)
                           for k, v in rec["runs"].items()}
        rec["runs"]["reference"] = base
        for k, (vals, _) in rec["compared"].items():
            harness.say("seed %d %-22s %s" % (seed, k, " ".join(
                "%s=%.3g" % kv for kv in vals.items())))
        out["seeds"].append(rec)
        _flush(out, args)


def calibrate_serve(cfg, wl, ref_mod, drv_mod, args, out):
    engine = None
    ref = ref_mod.Reference(
        cfg, pad_to=wl["prompt_len"]["max"] + wl["new_tokens"]["max"],
        new_tokens=wl["new_tokens"]["max"])
    k = wl["check_requests"]
    # every lane is occupied from the start, as in a run; the window opens at
    # the first completion, and the requests that END in it are judged
    wl = dict(wl, warm_completions=1)
    for seed in _seeds(args.seeds, args.base):
        drv = drv_mod.Driver(cfg=cfg, workload=wl, seed=seed,
                             reference=ref_mod)
        drv.engine = engine     # one engine, warmed once, serves every seed
        drv.setup()
        m = drv.window(args.seconds)
        engine = drv.engine
        drv.drain()
        weights = ref_mod.make_weights(cfg, seed)
        ok = [r for r in drv.sample if r.error is None]
        rec = {"seed": seed, "attempted": m["attempted"],
               "failed": m["failed"], "end_to_end": m["end_to_end"],
               "prompts": [len(drv.requests[r.index][0]) for r in ok]}
        for who in (None,) + tuple(ref_mod.CONTROLS):
            per = [float(ref.gaps(weights, drv.requests[r.index][0], r.tokens,
                                  control=who).max()) for r in ok]
            # as a run's sample of `check_requests` would read them
            rec[who or "engine"] = {"per_request": per, "samples": [
                max(per[j:j + k]) for j in range(0, len(per) - k + 1, k)]}
            harness.say("seed %d %-9s widest gap over %d requests %.4g; "
                        "samples of %d: %s" % (
                            seed, who or "engine", len(per), max(per), k,
                            ["%.4g" % g for g in rec[who or "engine"]["samples"]]))
        out["seeds"].append(rec)
        _flush(out, args)


def _flush(out, args):
    d = os.path.join(ROOT, "chiprun_out")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "calibrate_%s.json" % args.workload), "w") as f:
        json.dump(out, f)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base", type=int, default=2100000011)
    ap.add_argument("--controls", type=int, default=3,
                    help="train: seeds that also run the controls and faults")
    ap.add_argument("--no-program", action="store_true",
                    help="train: the reference, controls and faults only")
    ap.add_argument("--seconds", type=float, default=12.0,
                    help="serve: the window whose finished requests are judged")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--spec")
    ap.add_argument("--data-dir")
    args = ap.parse_args(argv)
    files = harness.Files(args.spec, [args.data_dir] if args.data_dir else [])
    cell = files.cell(args.workload)
    wl = files.load_json("workloads", args.workload)
    cfg = files.load_json("configs", cell["config"])
    info = harness.device_info(cell["chips"], args.rehearse)
    harness.compile_cache()
    out = {"cell": args.workload, "device": info, "seeds": []}
    ref_mod = files.load_module("references", cfg["reference"])
    drv_mod = files.load_module("drivers", cfg["driver"])
    fn = {"train": calibrate_train, "serve": calibrate_serve}[wl["kind"]]
    fn(cfg, wl, ref_mod, drv_mod, args, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
