"""RAG traffic for the benchmark, made from the seed.

A mix file beside this one (``<traffic>.json``) holds every parameter; this
one generator reads them all.  The corpus is a set of chunked passages with
lognormal lengths rounded to whole pages (the length model of the program's
corpus module, copied so that later changes to the program cannot move the
yardstick).  The passages sit in neighbourhoods of ``top_k``: the members
of a neighbourhood embed close to one centre, far from every other
neighbourhood, and a query embeds as its neighbourhood's centre plus noise,
so retrieval through the serving system's index returns exactly that
neighbourhood.

What the seed changes and what it does not.  The neighbourhoods' passage
lengths are laid out by rank from the mix alone, and so is the sequence of
ranks the requests target; the seed only shuffles that sequence inside
consecutive blocks of ``shuffle_block`` requests, and draws the embeddings,
the token ids and the questions.  So every seed serves the same prompt
sizes, block by block, in another order, and a window of a fixed length
serves nearly the same work whatever the seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

EMBED_DIM = 32
NEIGHBOUR_SPREAD = 0.02        # members' distance from their centre
QUERY_NOISE = 0.01
LAYOUT_STREAM = 0x5EED         # fixed: the layout is the mix's, not the seed's


@dataclasses.dataclass
class Passages:
    vectors: np.ndarray            # (N, EMBED_DIM) unit vectors
    tokens: List[np.ndarray]       # int32 token ids per passage
    lengths: np.ndarray            # (N,) tokens per passage; rank r owns
                                   # passages [r * top_k, (r + 1) * top_k)


@dataclasses.dataclass
class Query:
    target: int                    # the neighbourhood's rank
    vector: np.ndarray             # (EMBED_DIM,) query embedding
    question: np.ndarray           # int32 question token ids


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _layout_rng(mix: dict, stream: int) -> np.random.Generator:
    return np.random.default_rng([LAYOUT_STREAM, stream, mix["passages"], mix["top_k"]])


def passage_lengths(mix: dict) -> np.ndarray:
    """Every passage's length, by rank: lognormal quantiles (median
    ``median_tokens``, shape ``sigma``) rounded to ``token_multiple`` and
    clipped to [``min_tokens``, ``max_tokens``], laid out by a permutation
    of the mix's own.  One passage always sits at the cap, so the widest
    request the runtime sizes its tables for is the same for every seed."""
    n = mix["passages"]
    if n % mix["top_k"]:
        raise ValueError("passages must be a multiple of top_k")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = mix["median_tokens"] * np.exp(mix["sigma"] * z)
    m = mix["token_multiple"]
    lens = np.clip(m * np.round(raw / m), mix["min_tokens"], mix["max_tokens"])
    lens[-1] = mix["max_tokens"]
    return _layout_rng(mix, 0).permutation(lens.astype(np.int64))


def neighbourhoods(mix: dict) -> int:
    return mix["passages"] // mix["top_k"]


def make_passages(mix: dict, vocab: int, seed: int) -> Passages:
    rng = _rng(seed, 0)
    k, g = mix["top_k"], neighbourhoods(mix)
    centres = rng.normal(size=(g, EMBED_DIM))
    centres /= np.linalg.norm(centres, axis=1, keepdims=True)
    off = rng.normal(size=(g, k, EMBED_DIM))
    off *= NEIGHBOUR_SPREAD / np.linalg.norm(off, axis=2, keepdims=True)
    vecs = (centres[:, None] + off).reshape(g * k, EMBED_DIM)
    vecs = (vecs / np.linalg.norm(vecs, axis=1, keepdims=True)).astype(np.float32)
    lens = passage_lengths(mix)
    flat = rng.integers(0, vocab, size=int(lens.sum())).astype(np.int32)
    toks = np.split(flat, np.cumsum(lens)[:-1])
    return Passages(vecs, toks, lens)


def target_ranks(mix: dict, count: int) -> np.ndarray:
    """The mix's sequence of targeted ranks, before the seed's shuffle.

    ``targets: "unique"`` walks the ranks once each, so no two requests, set-up
    included, share a passage.  ``targets: "zipf"`` draws from Zipf(``zipf_s``)
    popularity over the ranks (rank 0 the most popular)."""
    g = neighbourhoods(mix)
    if mix["targets"] == "unique":
        if count > g:
            raise RuntimeError("unique traffic ran out of passages")
        return np.arange(count)
    if mix["targets"] == "zipf":
        p = 1.0 / np.arange(1, g + 1, dtype=np.float64) ** mix["zipf_s"]
        return _layout_rng(mix, 1).choice(g, size=count, p=p / p.sum())
    raise ValueError(f"unknown targets {mix['targets']!r}")


class Traffic:
    """The mix's request stream over one corpus.

    ``fill(n)`` gives the set-up's requests: the n most popular ranks once
    each (zipf), or the first n ranks (unique).  ``stream()`` then gives the
    window's, the mix's rank sequence shuffled by the seed inside blocks of
    ``shuffle_block`` counted from the window's start."""

    def __init__(self, mix: dict, passages: Passages, vocab: int, seed: int):
        self.mix = mix
        self.passages = passages
        self.vocab = vocab
        self._rng = _rng(seed, 1)
        self._filled = 0

    def _query(self, rank: int) -> Query:
        k = self.mix["top_k"]
        centre = self.passages.vectors[rank * k:(rank + 1) * k].mean(axis=0)
        noise = self._rng.normal(scale=QUERY_NOISE, size=EMBED_DIM)
        q = (centre / np.linalg.norm(centre) + noise).astype(np.float32)
        question = self._rng.integers(0, self.vocab, self.mix["question_tokens"])
        return Query(int(rank), q, question.astype(np.int32))

    def fill(self, n: int) -> List[Query]:
        self._filled = n
        return [self._query(r) for r in range(n)]

    def stream(self) -> Iterator[Query]:
        block = self.mix["shuffle_block"]
        start = self._filled if self.mix["targets"] == "unique" else 0
        done = 0
        while True:
            ranks = target_ranks(self.mix, start + done + block)[start + done:]
            for r in self._rng.permutation(ranks):
                yield self._query(r)
            done += block
