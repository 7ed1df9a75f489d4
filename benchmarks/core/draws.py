"""Seeded inputs. Request lengths are taken by inverse CDF over a
stratified grid of the unit interval and the seed shuffles their order
(and makes every token): every seed gives the same histogram of the
stated law, so sampling noise between seeds is gone and the law is not."""

from statistics import NormalDist

import numpy as np


def seed32(seed, salt=0):
    """A numpy seed from any whole `--seed` (the driver's pass 2**31)."""
    return (int(seed) * 1000003 + salt * 7919 + 12345) % (2 ** 32)


def grid(n):
    """Midpoints of `n` equal strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lognormal_clipped(n, median, sigma, low, high):
    """`n` whole numbers of a log-normal law (given median, sigma of the
    log), clipped to [low, high], one from each stratum, ascending."""
    z = np.array([NormalDist().inv_cdf(u) for u in grid(n)])
    x = np.rint(median * np.exp(sigma * z)).astype(np.int64)
    return np.clip(x, low, high)


def backlog_lengths(n, epoch, seed, prompt, output):
    """(prompt lengths, output lengths) of a backlog of `n` requests, in
    the order they are queued: consecutive epochs of `epoch` requests.
    Every epoch holds the SAME lengths, one from each of `epoch` strata
    of each law, and the seed shuffles every epoch's prompts and outputs
    by itself. So a seed changes the order of arrival and which prompt
    meets which output, and any stretch of whole epochs is the same work
    under every seed; only the epochs cut by the window's edges differ.
    `prompt`/`output` are lognormal_clipped's parameters."""
    rs = np.random.RandomState(seed32(seed, 51))
    p = lognormal_clipped(epoch, **prompt)
    o = lognormal_clipped(epoch, **output)
    epochs = -(-n // epoch)
    plen = np.concatenate([p[rs.permutation(epoch)] for _ in range(epochs)])
    olen = np.concatenate([o[rs.permutation(epoch)] for _ in range(epochs)])
    return plen[:n], olen[:n]


def zipf_cdf(vocab, exponent):
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return np.cumsum(w / w.sum())


class TokenStream:
    """An endless stream of distinct (rows, seq + 1) int32 batches made
    on the host from the seed: tokens from a Zipf unigram law, and with
    probability `successor_share` a token is instead the fixed successor
    of the one before it (a seeded permutation of the vocabulary), so
    that the loss has something to keep learning all through a run."""

    def __init__(self, seed, vocab, rows, seq, exponent=1.0,
                 successor_share=0.5):
        self.rs = np.random.RandomState(seed32(seed, 11))
        self.cdf = zipf_cdf(vocab, exponent)
        self.successor = np.random.RandomState(
            seed32(seed, 12)).permutation(vocab).astype(np.int32)
        self.rows, self.width, self.vocab = rows, seq + 1, vocab
        self.share = successor_share
        self.made = 0

    def __iter__(self):
        return self

    def __next__(self):
        u = self.rs.random_sample((self.rows, self.width))
        ids = np.minimum(np.searchsorted(self.cdf, u),
                         self.vocab - 1).astype(np.int32)
        if self.share > 0:
            follow = self.rs.random_sample(ids.shape) < self.share
            for t in range(1, self.width):
                col = follow[:, t]
                ids[col, t] = self.successor[ids[col, t - 1]]
        self.made += 1
        return {"input_ids": ids}


def prompt_tokens(lengths, vocab, seed):
    rs = np.random.RandomState(seed32(seed, 21))
    return [rs.randint(0, vocab, (int(n),)).astype(np.int32)
            for n in lengths]
