import os

from benchmark import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPEC = os.path.join(DATA, "BENCHMARK.json")
TRAIN, SERVE = "toy_bert_b4_s32", "toy_gpt2_chat_c4"


def rehearse(cell, seed=11, seconds=1.0, trace=0, spec=SPEC, data_dirs=None):
    """One run of the harness with the look for a chip skipped; returns the
    result and prints no result line."""
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace)], spec_path=spec,
                    data_dirs=data_dirs or [DATA], rehearse=True)
