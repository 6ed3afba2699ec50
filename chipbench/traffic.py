"""The one general traffic generator.  A mix is a data file under ``traffic/``;
this module turns its parameters and the seed into requests or batches.

A request mix is one fixed schedule, the same for every seed: sizes are the
evenly spaced quantiles of the mix's distributions and gaps the evenly spaced
quantiles of the exponential distribution, put into the order that the mix's
``order_seed`` draws, in which every run of eight holds one item from each
eighth of the range.  So the gaps have the exponential distribution's shape
but are not independent draws: no stretch of the window is much busier than
another, and a tail read under this schedule is lower than under Poisson
arrivals of the same rate.  The run's seed draws the token ids (and the
weights), so two seeds do the same work at the same moments, and a metric's
spread over seeds is the system's, not the draw's.
"""
from __future__ import annotations

import numpy as np


def _quantiles(spec, n):
    """n evenly spaced quantiles of a size distribution, as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if spec["dist"] == "loguniform":
        vals = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi - lo)
    elif spec["dist"] == "fixed":
        vals = np.full(n, lo)
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def _block_order(n, rng, block=8):
    """A seeded order of n evenly spaced quantiles in which every run of
    ``block`` items holds one quantile from each ``block``-th of the range: the
    seed moves items within and between such blocks, so the load of any stretch
    of the window is the same for every seed."""
    nb = -(-n // block)
    order = []
    for b in rng.permutation(nb):
        members = [j * nb + b for j in range(block) if j * nb + b < n]
        order.extend(rng.permutation(members))
    return np.asarray(order, np.int64)


def _rng(seed, stream):
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


def due_times(rate_per_s, seconds, order_seed):
    """Due times in [0, seconds): exponential-quantile gaps in the mix's order,
    scaled so that the last request is due just inside the window."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)          # mean 1
    gaps = gaps / gaps.sum() * (seconds * n / (n + 0.5))
    gaps = gaps[_block_order(n, _rng(order_seed, 1))]
    return np.cumsum(gaps) - gaps[0] * 0.5


def make_requests(mix, seed, vocab, seconds):
    """-> list of dicts {due_s|None, prompt (int32 array), max_new}.

    ``closed_loop``: a long list the clients draw from in order (due_s None).
    ``open_loop_schedule``: one request per due time in the window."""
    order_seed = mix["order_seed"]
    if mix["driver"] == "open_loop_schedule":
        due = due_times(mix["rate_per_s"], seconds, order_seed)
        n = len(due)
        n_sizes = n
    elif mix["driver"] == "closed_loop":
        n_sizes = int(mix["distinct_sizes"])
        n = n_sizes * 16
        due = [None] * n
    else:
        raise ValueError(f"driver {mix['driver']!r} makes no requests")
    plens = _quantiles(mix["prompt_tokens"], n_sizes)
    olens = _quantiles(mix["output_tokens"], n_sizes)
    order = _rng(order_seed, 2)
    # independent orders, so long prompts do not always get long answers
    plens = np.resize(plens[_block_order(n_sizes, order)], n)
    olens = np.resize(olens[_block_order(n_sizes, order)], n)
    rng = _rng(seed, 4)
    shared = rng.integers(0, vocab, int(mix.get("shared_prefix_tokens", 0)),
                          dtype=np.int32)
    out = []
    for i in range(n):
        body = rng.integers(0, vocab, int(plens[i]) - len(shared), dtype=np.int32)
        out.append({"due_s": None if due[i] is None else float(due[i]),
                    "prompt": np.concatenate([shared, body]),
                    "max_new": int(olens[i])})
    return out


def make_train_ring(mix, seed, vocab, chips):
    """-> list of ``ring_dispatches`` dispatch batches, each a tuple
    (ids, segments, labels) of (steps_per_dispatch, rows, seq) int32 arrays.
    Every row differs; a label is a seeded permutation of its token id."""
    rng = _rng(seed, 3)
    k, seq = int(mix["steps_per_dispatch"]), int(mix["seq"])
    rows = int(mix["rows_per_chip"]) * chips
    perm = rng.permutation(vocab).astype(np.int32)
    ring = []
    for _ in range(int(mix["ring_dispatches"])):
        ids = rng.integers(0, vocab, (k, rows, seq), dtype=np.int32)
        ring.append((ids, np.zeros_like(ids), perm[ids]))
    return ring


def make_work(mix, seed, vocab, seconds, chips):
    """What a cell's window consumes: requests or a ring of batches."""
    if mix["driver"] == "train_ring":
        return make_train_ring(mix, seed, vocab, chips)
    return make_requests(mix, seed, vocab, seconds)
