"""The one generator of inputs. A traffic mix is a file of parameters
under ``traffic/``; everything is drawn from ``--seed``.

Every seed gets the same work in another order: the serving mixes draw
from a fixed grid of (prompt, output) lengths that the seed shuffles, so
that runs differ by their order and their token ids, not by how much
they ask of the system.
"""
import numpy as np


def _rng(seed, *salt):
    # --seed may exceed 2**31; RandomState takes 32 unsigned bits a word
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    return np.random.RandomState(words + [int(s) & 0xFFFFFFFF for s in salt])


def batches(params, seed, vocab, chips):
    """``pool_batches`` host batches of (tokens, (mlm labels, nsp labels))
    at the global batch: every row differs, every ``pad_every``-th row
    ends in padding (id 0) so that the valid-length mask has work."""
    rng = _rng(seed, 1)
    seq = params["seq_length"]
    rows = params["batch_per_chip"] * chips
    tail = seq - int(seq * params["pad_tail_fraction"])
    out = []
    for _ in range(params["pool_batches"]):
        tokens = rng.randint(1, vocab, (rows, seq)).astype(np.int32)
        tokens[::params["pad_every"], tail:] = 0
        mlm = rng.randint(1, vocab, (rows, seq)).astype(np.int32)
        nsp = rng.randint(0, 2, (rows,)).astype(np.int32)
        out.append((tokens, (mlm, nsp)))
    return out


def _levels(spec):
    """``levels`` lengths spaced evenly in the logarithm between ``low``
    and ``high``: the midpoints of a log-uniform distribution's strata."""
    lo, hi, n = spec["low"], spec["high"], spec["levels"]
    return [int(round(lo * (hi / lo) ** ((i + 0.5) / n))) for i in range(n)]


def length_grid(params):
    return [(p, o) for p in _levels(params["prompt_tokens"])
            for o in _levels(params["output_tokens"])]


def requests(params, seed, vocab, count):
    """The first ``count`` requests of the seed's endless sequence: the
    grid of lengths in an order the seed shuffles, again and again, each
    with its own prompt ids. Client ``c`` of ``n`` sends requests
    ``c, c + n, ...``."""
    grid = length_grid(params)
    out = []
    lap = 0
    while len(out) < count:
        order = _rng(seed, 2, lap).permutation(len(grid))
        for g in order:
            p, o = grid[g]
            i = len(out)
            ids = _rng(seed, 3, i).randint(1, vocab, p).tolist()
            out.append({"id": i, "prompt": ids, "max_new_tokens": o})
            if len(out) == count:
                break
        lap += 1
    return out
