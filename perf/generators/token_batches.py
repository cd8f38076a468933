"""The general generator of pretraining batches: a mix is a file of
parameters.

A traffic file's `params`: {"batch": b, "seq": s}. Batch i is b sequences
of s token ids drawn uniformly over the vocabulary from a numpy Generator
seeded by (seed, i): a fresh batch every step, the same for the same
seed. The labels are the ids moved one place left (the last label wraps
round; it is one token in s and both the trainer and the reference get
the same one).

Uniform tokens make the loss sit near ln(vocab) and move it little; the
speed of a dense step does not depend on what the tokens are.
"""
import numpy as np


class Batches:
    def __init__(self, params, seed, vocab_size):
        self.shape = (int(params["batch"]), int(params["seq"]))
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        self.tokens_per_step = self.shape[0] * self.shape[1]

    def batch(self, i):
        rng = np.random.default_rng([self.seed, int(i)])
        ids = rng.integers(0, self.vocab, self.shape, dtype=np.int64)
        return ids, np.roll(ids, -1, axis=1)


def make(params, seed, vocab_size):
    return Batches(params, seed, vocab_size)
