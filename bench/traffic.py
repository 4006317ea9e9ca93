"""The one general generator of the benchmark's inputs.

A traffic file (``traffic/<mix>.json``) gives the parameters; this
module turns them and ``--seed`` into requests or training batches.

Every seed gets the same amount of work.  Arrival times are one sample
path of the mix's arrival process, fixed by the file; lengths are the
quantiles of their distributions at evenly spaced probabilities; the
seed only deals the lengths out in another order and picks the token
ids.  A backlog is cut into blocks of
``block`` requests, each block one such stratified set, so any whole
number of blocks holds the same work.

A length is drawn from the family its ``dist`` names: ``exponential``
(by its ``mean``) or ``lognormal`` (by its ``median`` and ``sigma``),
rounded to whole tokens and clipped to ``min`` and ``max``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    due: float            # seconds after the window opens
    prompt: tuple
    out: int


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, int(seed) >> 32,
                                  int(seed) & 0xFFFFFFFF])


def lengths(spec: dict, n: int) -> np.ndarray:
    """n stratified draws of a clipped length of the spec's family."""
    probs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "exponential":
        raw = -np.log1p(-probs) * spec["mean"]
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in probs])
        raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def sizes(t: dict, n: int) -> np.ndarray:
    """n (prompt, output) length pairs, the same for every seed: the two
    stratified lists paired by a fixed shuffle."""
    out = lengths(t["output"], n)[rng(0, 5).permutation(n)]
    return np.stack([lengths(t["prompt"], n), out], axis=1)


def _tokens(r: np.random.Generator, n: int, vocab: int) -> tuple:
    return tuple(int(t) for t in r.integers(0, vocab, n))


def arrival_times(t: dict, seconds: float) -> np.ndarray:
    """The mix's one sample path of a Poisson process at ``rate_per_s``
    over the window: independent exponential gaps drawn from the
    traffic file's ``arrival_seed``, the same for every ``--seed``, as a
    recorded trace is."""
    r = rng(t["arrival_seed"], 3)
    due, now = [], 0.0
    while True:
        now += float(r.exponential(1.0 / t["rate_per_s"]))
        if now >= seconds:
            return np.array(due)
        due.append(now)


def open_loop(t: dict, seed: int, seconds: float, vocab: int) -> list:
    """The mix's arrivals over the window; the seed deals the stratified
    sizes to them in its own order and draws the token ids."""
    due = arrival_times(t, seconds)
    n = len(due)
    r = rng(seed, 0)
    p_len, o_len = r.permutation(sizes(t, n)).T
    return [Req(i, float(due[i]), _tokens(r, int(p_len[i]), vocab),
                int(o_len[i])) for i in range(n)]


def backlog_block(t: dict, seed: int, block_index: int, vocab: int,
                  first_rid: int) -> list:
    """One block of a backlog: every request due at once (due 0)."""
    n = t["block"]
    r = rng(seed, 1000 + block_index)
    p_len, o_len = r.permutation(sizes(t, n)).T
    return [Req(first_rid + i, 0.0, _tokens(r, int(p_len[i]), vocab),
                int(o_len[i])) for i in range(n)]


def n_requests(t: dict, seconds: float) -> int:
    """How many requests one stratified set of the mix holds."""
    if t["arrivals"] == "backlog":
        return t["block"]
    return len(arrival_times(t, seconds))


def warm_prompts(t: dict, vocab: int, buckets, seconds: float) -> list:
    """One prompt per prefill bucket that the mix's prompts hit."""
    lens = lengths(t["prompt"], n_requests(t, seconds))
    r = rng(0, 99)
    used = sorted({min(b for b in buckets if b >= n) for n in lens})
    return [_tokens(r, min(b, int(lens.max())), vocab) for b in used]


def train_batches(t: dict, seed: int, vocab: int, n: int):
    """n distinct (tokens, labels) batches of random ids, as one device
    array (n, batch, seq + 1); every row differs."""
    import jax
    import jax.numpy as jnp
    words = np.array([int(seed) >> 32, int(seed) & 0xFFFFFFFF], np.uint32)

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(2), words[0]), words[1])
        return jax.random.randint(key, (n, t["batch"], t["seq"] + 1), 0,
                                  vocab, jnp.int32)
    return make(words)
