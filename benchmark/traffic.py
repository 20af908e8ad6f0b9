"""The one general traffic generator: reads a cell's parameters (a data file
under `workloads/`) and draws inputs from the seed. No cell has code here.

Every seed gets the SAME sizes in the SAME order, and other token ids: a seed
changes the inputs and not the work. (With the sizes in an order drawn from
the seed, which requests end inside a 50 s window changed with the seed: the
serve cell's tokens/s spread by 10-14 % between seeds and by nothing between
two runs of one seed; my chip runs, PR 26.)
"""
import numpy as np


def _rng(seed, salt):
    return np.random.RandomState((int(seed) * 1000003 + salt) % (2 ** 32))


def mlm_batch(params, cfg, seed):
    """One masked-LM pretraining batch as `chip_smoke._bert_batch` draws it:
    ids uniform over the vocabulary, `masked` distinct positions a row, the
    labels the original ids there, one NSP label a row. All rows differ."""
    B, S, M = params["batch"], params["seq_len"], params["masked"]
    rng = _rng(seed, 1)
    ids = rng.randint(0, cfg["vocab_size"], (B, S)).astype(np.int32)
    pos = np.stack([rng.choice(S, M, replace=False)
                    for _ in range(B)]).astype(np.int32)
    mlm = np.take_along_axis(ids, pos, axis=1).astype(np.int32)
    nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)
    return ids, pos, mlm, nsp


def _lengths(spec, n, rng):
    """n lengths: the quantile grid of the distribution, shuffled by `rng`."""
    lo, hi = spec["min"], spec["max"]
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "loguniform":
        vals = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif spec["dist"] == "fixed":
        vals = np.full(n, lo, float)
    else:
        raise ValueError("unknown length distribution %r" % spec["dist"])
    vals = np.clip(np.rint(vals), lo, hi).astype(int)
    return vals[rng.permutation(n)]


def requests(params, cfg, seed):
    """-> list of (prompt ids int32 array, new_tokens). `pool` requests in
    cycles of `cycle`: each cycle holds the same multiset of prompt lengths
    (the quantile grid of `prompt_len`), in one order for every seed; ids are
    drawn from the seed, uniform over the vocabulary."""
    rng = _rng(seed, 2)
    order = _rng(0, 3)
    pool, cycle = params["pool"], params["cycle"]

    def cycles(spec):
        return np.concatenate([_lengths(spec, cycle, order)
                               for _ in range(-(-pool // cycle))])[:pool]
    lens, news = cycles(params["prompt_len"]), cycles(params["new_tokens"])
    flat = rng.randint(0, cfg["vocab_size"], int(lens.sum())).astype(np.int32)
    ends = np.cumsum(lens)
    return [(flat[e - n:e], int(g)) for e, n, g in zip(ends, lens, news)]
