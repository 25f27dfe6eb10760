"""One generator for every traffic mix: ``bench/traffic/<mix>.json``.

A mix is data.  Each request is an optional shared prefix (one of a few
seeded documents, chosen by popularity) followed by a part of its own,
and asks for a drawn number of output tokens.  Lengths are drawn as a
stratified sample: every block of ``block`` requests (one ``serve()``
call) holds the same multiset of lengths, the quantiles of the mix's
distribution at ``(i + 0.5) / block``.  The order of that multiset and
the pairing of prompt and output lengths change from block to block but
not with the seed; the seed draws every token.  So every seed offers the
same work, and a window's length does not hang on the order in which a
closed batch drains.  Where prompts start with a document, each block
holds the same pairs of document (with the popularity's counts) and own
length, in its own order: the prompt lengths a window can send are a
fixed set, whatever its number of blocks (``prompt_shapes``).

Length specs (all bounds inclusive, ``multiple`` rounds up):

    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"dist": "uniform", "min": a, "max": b}
    {"dist": "choice", "values": [..]}     # equal shares, cycled
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Req:
    """One generated request: its prompt, its output budget, and which
    shared document (or -1) its prompt starts with."""

    prompt: np.ndarray
    max_new_tokens: int
    doc: int = -1

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """The ``n`` stratified lengths of one block, in ascending order."""
    dist = spec["dist"]
    mult = int(spec.get("multiple", 1))
    if dist == "choice":
        vals = sorted(int(v) for v in spec["values"])
        out = [vals[i * len(vals) // n] for i in range(n)]
    else:
        lo, hi = int(spec["min"]), int(spec["max"])
        qs = [(i + 0.5) / n for i in range(n)]
        if dist == "lognormal":
            mu, sig = np.log(spec["median"]), float(spec["sigma"])
            raw = [float(np.exp(mu + sig * NormalDist().inv_cdf(q)))
                   for q in qs]
        elif dist == "uniform":
            raw = [lo + q * (hi - lo) for q in qs]
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        out = [min(hi, max(lo, int(round(x)))) for x in raw]
    return sorted(_round_up(v, mult) for v in out)


def popularity_counts(weights: np.ndarray, n: int) -> np.ndarray:
    """Requests per document in a block of ``n``: the largest-remainder
    apportionment of ``n * weights`` (same counts for every seed)."""
    share = n * weights / weights.sum()
    counts = np.floor(share).astype(int)
    rest = n - counts.sum()
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def documents(mix: dict) -> Optional[dict]:
    """The mix's shared documents: their lengths (one per popularity rank,
    fixed for every seed) and popularity weights; None without a prefix."""
    pre = mix.get("prefix")
    if not pre:
        return None
    n = int(pre["documents"])
    lens = quantile_lengths(pre["length"], n)
    # a fixed, seed-independent interleave, so rank and length are not
    # sorted together
    lens = [lens[i] for i in np.random.RandomState(0).permutation(n)]
    zipf = float(pre.get("zipf", 0.0))
    weights = 1.0 / np.arange(1, n + 1) ** zipf
    return {"lengths": lens, "weights": weights / weights.sum()}


class Stream:
    """The requests a window draws from, one block (``serve()`` call) at
    a time, without end.  Block ``b``'s lengths depend on ``b`` alone, its
    tokens on the seed and ``b``."""

    def __init__(self, mix: dict, seed: int, vocab: int, block: int):
        self.seed, self.vocab, self.block_size = seed, vocab, block
        self.docs = documents(mix)
        rng = np.random.Generator(np.random.PCG64([seed, 1 << 20]))
        self.doc_tokens = ([self._tokens(rng, n) for n in self.docs["lengths"]]
                           if self.docs else [])
        self.own = quantile_lengths(mix["prompt"], block)
        self.outs = quantile_lengths(mix["output"], block)
        self.doc_of = np.full(block, -1)
        if self.docs:
            counts = popularity_counts(self.docs["weights"], block)
            self.doc_of = np.repeat(np.arange(len(counts)), counts)
            # a fixed, seed-independent interleave, so popularity and own
            # length are not sorted together
            self.own = [self.own[i]
                        for i in np.random.RandomState(1).permutation(block)]

    def _tokens(self, rng, n: int) -> np.ndarray:
        return rng.integers(1, self.vocab, n, dtype=np.int64).astype(np.int32)

    def doc_len(self, d: int) -> int:
        return self.docs["lengths"][d] if d >= 0 else 0

    def prompt_shapes(self) -> List[tuple]:
        """Every (document length, own length) a block can send; a
        document length of 0 is no document."""
        return sorted({(self.doc_len(int(d)), int(o))
                       for d, o in zip(self.doc_of, self.own)})

    def block(self, b: int) -> List[Req]:
        n = self.block_size
        shape = np.random.Generator(np.random.PCG64([b, 1 << 21]))
        p_order, o_order = shape.permutation(n), shape.permutation(n)
        rng = np.random.Generator(np.random.PCG64([self.seed, b]))
        reqs = []
        for i in range(n):
            k = p_order[i]
            body = self._tokens(rng, self.own[k])
            d = int(self.doc_of[k])
            if d >= 0:
                body = np.concatenate([self.doc_tokens[d], body])
            reqs.append(Req(body, int(self.outs[o_order[i]]), d))
        return reqs
