"""The one general traffic generator: it reads a mix's data file,
`bench/traffic/<name>.json` (loaded by the harness), and makes the cell's
inputs from `--seed`.

Every seed gets the same work in another order: request sizes are a fixed
multiset (each value's share of the requests, rounded), and open-loop gaps
are the quantiles of the exponential distribution at the mix's rate,
shuffled.  So runs with different seeds differ in order, not in amount.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def multiset(dist: dict, n: int, r: np.random.Generator) -> np.ndarray:
    """n values with each value's share of `dist` ({"values", "shares"}),
    largest remainders rounded up, in a seeded order."""
    vals = np.asarray(dist["values"], np.int64)
    shares = np.asarray(dist.get("shares", [1.0] * len(vals)), np.float64)
    want = shares / shares.sum() * n
    cnt = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - cnt), kind="stable")[:n - cnt.sum()]:
        cnt[i] += 1
    out = np.repeat(vals, cnt)
    r.shuffle(out)
    return out


def open_loop(mix: dict, seed: int, seconds: float) -> list:
    """[(due_s, prompt_len, max_new)] for every request due in the window:
    Poisson arrivals at the mix's rate, as a fixed set of gaps."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    r = rng(seed, 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    r.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]          # the first request at the start
    due = due[due < seconds]
    n = len(due)
    prompts = multiset(mix["prompt_len"], n, r)
    outputs = multiset(mix["output_len"], n, r)
    return [(float(d), int(p), int(o)) for d, p, o in zip(due, prompts, outputs)]


def closed_loop(mix: dict, seed: int) -> list:
    """One list per client of [(prompt_len, max_new)], as many as a client
    can send in any window the mix allows."""
    c = mix["arrivals"]
    clients, per = int(c["clients"]), int(c["requests_per_client"])
    r = rng(seed, 1)
    prompts = multiset(mix["prompt_len"], clients * per, r)
    outputs = multiset(mix["output_len"], clients * per, r)
    pairs = list(zip(prompts.tolist(), outputs.tolist()))
    return [pairs[i * per:(i + 1) * per] for i in range(clients)]


def prompt_tokens(seed: int, lengths, vocab: int) -> list:
    """Token ids of each prompt, drawn uniformly over the vocabulary."""
    r = rng(seed, 2)
    return [r.integers(0, vocab, int(n)).astype(np.int32) for n in lengths]
