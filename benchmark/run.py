"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads BENCHMARK.json for the cell, finds its files by name (harness.py),
sets the cell up, warms every shape it will use (all of that is `setup_s`),
measures for `--seconds`, then checks what the timed path produced against the
plain reference. The LAST line of standard output is the result as one JSON
object; the numbers compared, each beside its limit, are the last lines of
standard error and the last key of the result. Exits non-zero, with no result,
where JAX finds no TPU, where the cell left the path it stands for, or where
the program is not importable. Takes no notice of BENCH_RUN.
"""
import time
T_START = time.time()   # set-up is counted from here: before any import

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, spec_path=None, data_dirs=None, rehearse=False):
    """`rehearse` (tests only) skips the look for a chip, drives the rest of
    the run and RETURNS the result: a rehearsal never prints a result line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark import harness
    try:
        files = harness.Files(spec_path, data_dirs)
        result = harness.run_cell(files, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  time.time() if rehearse else T_START,
                                  rehearse=rehearse)
    except harness.BenchError as e:
        harness.say("FAILED: %s" % e)
        if rehearse:
            raise
        return 1
    if rehearse:
        return result
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
