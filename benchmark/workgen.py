"""The one general traffic generator: every mix is a data file of parameters.

Serving traffic is the SAME work in every run. The lengths are a fixed,
stratified multiset: the quantiles of the distributions the traffic file
states, paired by a fixed rule. Their order and the arrival gaps (the
stratified quantiles of the exponential distribution) are drawn once, from the
traffic file's ``schedule_seed``; ``--seed`` makes the prompt tokens and the
weights. The order is
balanced: requests go out in blocks that are the same sets for every seed,
each with one request from every stratum of the answer lengths and an even
spread of prompt lengths, so no seed front-loads the long answers or the
short prompts.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def stratified_lengths(spec, n):
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``: a lognormal
    with the given ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        x = math.exp(mu + spec["sigma"] * _NORMAL.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def request_multiset(traffic, n):
    """The ``n`` (prompt length, answer length) pairs every run offers,
    whatever its seed. Prompt quantile i meets answer quantile i * stride
    mod n, with stride the integer nearest n / golden ratio that is coprime
    to n, so the two lengths are spread against each other evenly."""
    prompts = stratified_lengths(traffic["prompt_len"], n)
    answers = stratified_lengths(traffic["answer_len"], n)
    stride = max(1, round(n * 0.6180339887))
    while math.gcd(stride, n) != 1:
        stride += 1
    limit = traffic["max_total_len"]
    pairs = []
    for i in range(n):
        p, a = prompts[i], answers[(i * stride) % n]
        pairs.append((min(p, limit - a), a))
    return pairs


def balanced_order(pairs, rng, block):
    """The pairs in blocks of ``block`` that are the same for every seed:
    sorted by answer length and cut into ``block`` strata, each stratum
    sorted by prompt length; block j takes from stratum s the pair of
    prompt rank (j + s * shift) mod the number of blocks, so every block
    holds one pair of each answer stratum and an even spread of prompts.
    ``rng`` permutes the blocks and the order inside each.
    ``len(pairs)`` is a multiple of ``block``."""
    n_blocks, rest = divmod(len(pairs), block)
    if rest:
        raise ValueError(f"{len(pairs)} requests are not blocks of {block}")
    by_answer = sorted(pairs, key=lambda pa: (pa[1], pa[0]))
    strata = [sorted(by_answer[s * n_blocks:(s + 1) * n_blocks])
              for s in range(block)]
    shift = max(1, round(n_blocks * 0.6180339887))
    while math.gcd(shift, n_blocks) != 1:
        shift += 1
    out = []
    for j in rng.permutation(n_blocks):
        members = [strata[s][(j + s * shift) % n_blocks]
                   for s in range(block)]
        out.extend(members[k] for k in rng.permutation(block))
    return out


def poisson_due_times(n, rate, rng, block):
    """Due offsets in seconds of ``n`` arrivals at ``rate`` per second. The
    gaps of every span of ``block`` arrivals are the same multiset: the
    stratified quantiles of the exponential distribution, scaled to a mean
    of 1 / rate, in an order ``rng`` draws. So the gaps are a Poisson
    process's, and every span of ``block`` arrivals lasts block / rate."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / block)
                     for i in range(block)])
    gaps *= block / (rate * gaps.sum())
    out = []
    for j in range(n // block):
        out.extend(j * block / rate + np.cumsum(rng.permutation(gaps)))
    return [float(t) for t in out]


def open_loop_schedule(traffic, seed, duration_s, vocab_size):
    """Everything the generator sends in ``duration_s`` seconds, made before
    the first send: (due offset, prompt tokens, answer length) per request.
    The lengths, their order and the due times come from the traffic
    file's ``schedule_seed`` and are the same in every run; ``seed`` makes
    the prompt tokens (and, in the builder, the weights). On the chip the
    order alone moved the median latency by 4 % between seeds (PERF.md,
    PR 23): a short prompt's one-shot prefill stalls every slot, so where
    the short prompts fall IS the work."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    if traffic["sharing"] != "none":
        raise ValueError(f"unknown sharing {traffic['sharing']!r}")
    block = traffic["block_requests"]
    rate = traffic["rate_rps"]
    n = block * math.ceil(duration_s * rate / block)
    fixed = np.random.default_rng(traffic["schedule_seed"])
    pairs = balanced_order(request_multiset(traffic, n), fixed, block)
    due = poisson_due_times(n, rate, fixed, block)
    rng = np.random.default_rng(seed)
    return [(t, prompt_tokens(rng, p, vocab_size), a)
            for t, (p, a) in zip(due, pairs)]


def closed_loop_requests(traffic, seed, n, vocab_size):
    """``n`` requests (rounded up to whole blocks) for clients that each
    send their next when the last is answered: (prompt tokens, answer).
    Lengths and order from ``schedule_seed``, tokens from ``seed``."""
    if traffic["sharing"] != "none":
        raise ValueError(f"unknown sharing {traffic['sharing']!r}")
    block = traffic["clients"]
    n = block * math.ceil(n / block)
    fixed = np.random.default_rng(traffic["schedule_seed"])
    pairs = balanced_order(request_multiset(traffic, n), fixed, block)
    rng = np.random.default_rng(seed)
    return [(prompt_tokens(rng, p, vocab_size), a) for p, a in pairs]


def prompt_tokens(rng, length, vocab_size):
    # token 0 is left out: it is the pad id of the program's prefill feeds
    return [int(t) for t in rng.integers(1, vocab_size, length)]

