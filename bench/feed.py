"""The token feed: a seeded Markov stream, one per worker, whole phases.

Each worker's stream follows its own fixed random permutation of the
vocabulary (next token = perm[current]) and replaces a token by a
uniform draw with probability ``noise``: learnable structure, as in the
repository's synthetic ``token_stream``, generated for all workers,
rows and steps of a phase at once so that the generator never sets the
pace. Every seed gives the same shapes; only the tokens differ.
"""
from __future__ import annotations

import time

import numpy as np


class TokenBlocks:
    """Iterator of (K, M, B, S) int32 token blocks, one per phase."""

    def __init__(self, traffic, vocab: int, seed: int):
        self.k = traffic["phase_len"]
        self.m = traffic["workers"]
        self.b = traffic["batch"]
        self.s = traffic["seq"]
        self.noise = float(traffic["noise"])
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 0x5EED])
        self.perm = np.stack([self.rng.permutation(vocab)
                              for _ in range(self.m)]).astype(np.int32)
        self.host_s = 0.0
        self.steps = 0

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        t0 = time.perf_counter()
        shp = (self.k, self.m, self.b)
        flip = self.rng.random(shp + (self.s,)) < self.noise
        draw = self.rng.integers(0, self.vocab, shp + (self.s,), np.int32)
        rows = np.arange(self.m)[None, :, None]
        out = np.empty(shp + (self.s,), np.int32)
        out[..., 0] = draw[..., 0]
        for j in range(1, self.s):
            nxt = self.perm[rows, out[..., j - 1]]
            out[..., j] = np.where(flip[..., j], draw[..., j], nxt)
        self.host_s += time.perf_counter() - t0
        self.steps += self.k
        return out


class Feed:
    """The per-step batch stream ``PhaseEngine.run`` consumes. It yields
    whole phases only (a short last block would compile another phase
    length) and stops at the first phase boundary after ``stop()``
    returns True. ``first_block`` keeps a copy of the first phase's
    tokens for the reference."""

    def __init__(self, blocks: TokenBlocks, stop=lambda: False,
                 annotate=None):
        self.blocks = blocks
        self.stop = stop
        self.annotate = annotate
        self.first_block = None
        self._gen = self._steps()

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def _steps(self):
        while not self.stop():
            if self.annotate is not None:
                with self.annotate("bench.traffic"):
                    blk = next(self.blocks)
            else:
                blk = next(self.blocks)
            if self.first_block is None:
                self.first_block = blk.copy()
            for t in range(blk.shape[0]):
                yield {"tokens": blk[t]}
