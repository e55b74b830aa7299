"""The one general traffic generator. A mix is a data file of parameters
(perf/traffic/<mix>.json); this turns it and `--seed` into requests.

Every seed gets the same set of lengths, in an order of its own: the
lengths are the evenly spaced quantiles of the mix's clipped lognormal,
as many as the mix's pool holds, and `--seed` alone draws the order of
the prompts, the order of the outputs (each on its own, so the pairs
differ too) and the token ids. Two seeds differ in which request comes
before which, never in how much work the pool holds. A mix sets
`pool_size` to about what one run sends, so that a run goes once through
the whole set.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2) / n of a clipped lognormal
    (`median`, `sigma`, `min`, `max`)."""
    nd = NormalDist()
    out = np.empty(n, np.int64)
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = spec['median'] * math.exp(spec['sigma'] * z)
        out[i] = int(min(max(round(v), spec['min']), spec['max']))
    return out


class Request:
    __slots__ = ('index', 'prompt', 'max_new', 'sent', 'token_times',
                 'tokens', 'error', 'done_time', 'client', 'future')

    def __init__(self, index, prompt, max_new):
        self.index = index
        self.prompt = prompt
        self.max_new = int(max_new)
        self.sent = None
        self.token_times = []
        self.tokens = []
        self.error = None
        self.done_time = None
        self.client = None
        self.future = None


def closed_loop_pool(mix: dict, vocab: int, seed: int) -> list:
    """The pool that the clients draw their requests from, in order; a
    pool that runs out starts again from its head."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
    n = int(mix['pool_size'])
    prompts = rng.permutation(quantile_lengths(mix['prompt_tokens'], n))
    outputs = rng.permutation(quantile_lengths(mix['output_tokens'], n))
    return [Request(i, rng.integers(0, vocab, size=int(prompts[i]),
                                    dtype=np.int64).tolist(), outputs[i])
            for i in range(n)]


def train_tokens(vocab: int, seed: int, rows: int, seq: int) -> np.ndarray:
    """rows x (seq + 1) token ids, every row different."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
    return rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int64)
