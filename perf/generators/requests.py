"""The general generator of serving traffic: a mix is a file of parameters.

A traffic file's `params`:

  arrival     {"process": "closed", "clients": N}
                N callers, each sends its next request when its last one
                completes (first requests all at t=0);
              {"process": "open", "rate_per_s": r, "cv": c}
                independent users: interarrival gaps drawn from a gamma
                distribution with mean 1/r and coefficient of variation c
                (c = 1 is a Poisson process, c > 1 is burstier, as BurstGPT
                fits real arrivals), sent on schedule whatever the system
                does.
  prompt_len, output_len
              {"dist": "lognormal", "median": m, "sigma": s, "min": a,
               "max": b} | {"dist": "uniform", "min": a, "max": b} |
              {"dist": "fixed", "value": v}; lognormal draws are clipped
              to [min, max].
  max_total   prompt + output never exceeds it (the output is cut).
  prefix      optional sharing: {"pool": n, "len": <dist>, "share": p}.
              n prefixes are drawn once; a request starts with one of
              them (uniformly chosen) with probability p, and its own
              tokens follow. Leave it out for prompts that share nothing.
  stagger_first
              closed loop only: each client's FIRST request has its
              output cut to a uniform fraction, so the clients start out
              of phase, as a long-running population is, and not as one
              cohort.
  stratify    K (default 1 = independent draws). Gaps, prompt lengths and
              output lengths are each drawn by stratified sampling in
              blocks of K: every K successive draws take one value from
              each of the K equal-probability slices of the distribution,
              in a seeded random order. The distributions are exactly the
              ones named; what shrinks is the luck of the draw — how many
              requests and how much work fall into one window — so runs
              with different seeds offer nearly the same amount of work in
              another order.

Everything is drawn from numpy Generators seeded by `seed`: one stream
each for the gaps, the prompt lengths and the output lengths, consumed in
the order requests are issued, and one per request index for its tokens.
So request i is the same whatever the system's timing; in a closed loop
only WHICH client sends it depends on completion order.
"""
from statistics import NormalDist

import numpy as np
from scipy.special import gammaincinv

_NORMAL = NormalDist()


class _Strata:
    """Uniform numbers in [0, 1), one from each of `k` equal slices per
    block of k, the slices in a seeded random order."""

    def __init__(self, rng, k):
        self.rng, self.k, self.block = rng, int(k), []

    def next(self):
        if not self.block:
            order = self.rng.permutation(self.k)
            self.block = list((order + self.rng.uniform(size=self.k))
                              / self.k)
        return float(self.block.pop())


def _length(u, spec):
    """The length at quantile `u` of the distribution `spec` names."""
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "uniform":
        return int(spec["min"] + np.floor(u * (spec["max"] - spec["min"] + 1)))
    if kind == "lognormal":
        u = min(max(u, 1e-12), 1 - 1e-12)
        x = spec["median"] * np.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
        return int(np.clip(round(x), spec["min"], spec["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


class Request:
    __slots__ = ("index", "t_due", "prompt", "max_new", "client")

    def __init__(self, index, t_due, prompt, max_new, client):
        self.index, self.t_due, self.prompt = index, t_due, prompt
        self.max_new, self.client = max_new, client


class Stream:
    """`due(t)` hands over every request due by `t` (seconds since the
    stream started); `done(request, t)` tells a closed loop that a caller
    is free again."""

    def __init__(self, params, seed, vocab_size):
        self.p = params
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        self.issued = 0
        arr = params["arrival"]
        k = int(params.get("stratify", 1))

        def strata(stream, n=k):
            return _Strata(np.random.default_rng([self.seed, stream]), n)

        self._u_prompt, self._u_out = strata(5), strata(6)
        self.closed = arr["process"] == "closed"
        if self.closed:
            clients = int(arr["clients"])
            self._free = [(0.0, c) for c in range(clients)]
            self._u_phase = strata(4, clients)
        elif arr["process"] == "open":
            self._u_gap = strata(1)
            self._shape = 1.0 / float(arr.get("cv", 1.0)) ** 2
            self._scale = 1.0 / (float(arr["rate_per_s"]) * self._shape)
            self._next = self._gap()
        else:
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        pre = params.get("prefix")
        self._prefixes = []
        if pre:
            rng = np.random.default_rng([self.seed, 2])
            self._prefixes = [
                rng.integers(0, self.vocab, _length(rng.uniform(), pre["len"]))
                for _ in range(int(pre["pool"]))]

    def _gap(self):
        """Gamma-distributed, by the inverse of its distribution function."""
        return float(gammaincinv(self._shape, self._u_gap.next())
                     * self._scale)

    def _make(self, t_due, client):
        i = self.issued
        self.issued += 1
        rng = np.random.default_rng([self.seed, 3, i])
        n_prompt = _length(self._u_prompt.next(), self.p["prompt_len"])
        n_out = _length(self._u_out.next(), self.p["output_len"])
        n_out = max(1, min(n_out, int(self.p["max_total"]) - n_prompt))
        if self.closed and self.p.get("stagger_first") and t_due == 0.0:
            n_out = max(1, int(np.ceil(n_out * self._u_phase.next())))
        prompt = rng.integers(0, self.vocab, n_prompt)
        pre = self.p.get("prefix")
        if pre and rng.uniform() < pre["share"]:
            head = self._prefixes[int(rng.integers(len(self._prefixes)))]
            head = head[:n_prompt - 1]
            prompt[:head.size] = head
        return Request(i, t_due, prompt.astype(np.int64), n_out, client)

    def due(self, t):
        out = []
        if self.closed:
            while self._free and self._free[0][0] <= t:
                t_due, client = self._free.pop(0)
                out.append(self._make(t_due, client))
        else:
            while self._next <= t:
                out.append(self._make(self._next, None))
                self._next += self._gap()
        return out

    def next_due(self):
        """When the next request falls due, if that is already known."""
        if self.closed:
            return self._free[0][0] if self._free else None
        return self._next

    def done(self, request, t):
        if self.closed:
            self._free.append((t, request.client))


def make(params, seed, vocab_size):
    return Stream(params, seed, vocab_size)
