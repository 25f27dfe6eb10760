"""The seeded generator: it repeats per seed, every seed offers the same
work, and the lengths follow each mix's parameters."""

from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import CHAT, DOCS

MIXES = sorted(p.stem for p in (Path(traffic.__file__).parent
                                / "traffic").glob("*.json"))


def generate(mix, seed, vocab, block, blocks):
    """The first ``blocks`` blocks of the stream, in order."""
    s = traffic.Stream(mix, seed, vocab, block)
    return [r for b in range(blocks) for r in s.block(b)]


def lengths(reqs, mix):
    """The multisets of own prompt lengths, documents and outputs."""
    docs = traffic.documents(mix)
    own = [r.prompt_len - (docs["lengths"][r.doc] if r.doc >= 0 else 0)
           for r in reqs]
    return (sorted(own), sorted(r.doc for r in reqs),
            sorted(r.max_new_tokens for r in reqs))


@pytest.mark.parametrize("mix", MIXES)
def test_stream_repeats_per_seed(mix):
    m = traffic.load_mix(mix)
    a = generate(m, 2**31 + 7, 1000, 16, 3)
    b = generate(m, 2**31 + 7, 1000, 16, 3)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    m = traffic.load_mix(mix)
    a = generate(m, 1, 1000, 16, 2)
    b = generate(m, 2, 1000, 16, 2)
    assert [(r.prompt_len, r.max_new_tokens) for r in a] == \
        [(r.prompt_len, r.max_new_tokens) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # every block holds the same multiset of lengths, in its own order
    assert lengths(a[:16], m) == lengths(a[16:], m)
    assert [r.max_new_tokens for r in a[:16]] != \
        [r.max_new_tokens for r in a[16:]]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_mix(mix):
    m = traffic.load_mix(mix)
    reqs = generate(m, 5, 1000, 64, 1)
    out = m["output"]
    assert all(out["min"] <= r.max_new_tokens <= out["max"] for r in reqs)
    own = [r.prompt_len - (traffic.documents(m)["lengths"][r.doc]
                           if r.doc >= 0 else 0) for r in reqs]
    p = m["prompt"]
    if p["dist"] == "choice":
        assert set(own) == set(p["values"])
    else:
        assert all(p["min"] <= n <= p["max"] for n in own)
        if p["dist"] == "lognormal":
            assert np.median(own) == pytest.approx(p["median"], rel=0.05)
    assert all(((r.prompt >= 1) & (r.prompt < 1000)).all() for r in reqs)


def test_documents_and_popularity():
    m = dict(DOCS)
    docs = traffic.documents(m)
    assert len(docs["lengths"]) == 3
    assert all(33 <= n <= 70 for n in docs["lengths"])
    assert any(n % 16 for n in docs["lengths"])
    reqs = generate(m, 3, 1000, 12, 2)
    counts = Counter(r.doc for r in reqs[:12])
    want = traffic.popularity_counts(docs["weights"], 12)
    assert [counts[d] for d in range(3)] == list(want)
    assert sum(want) == 12 and want[0] > want[2]
    # requests of one document share its tokens
    same = [r for r in reqs if r.doc == 0]
    n = docs["lengths"][0]
    assert all(np.array_equal(r.prompt[:n], same[0].prompt[:n]) for r in same)


def test_quantile_lengths():
    spec = {"dist": "uniform", "min": 10, "max": 20}
    assert traffic.quantile_lengths(spec, 2) == [12, 18]
    spec = {"dist": "choice", "values": [8, 4]}
    assert traffic.quantile_lengths(spec, 4) == [4, 4, 8, 8]
    spec = dict(CHAT["prompt"])
    got = traffic.quantile_lengths(spec, 101)
    assert got[50] == spec["median"] and got == sorted(got)


@pytest.mark.parametrize("mix", MIXES + ["tiny-docs"])
def test_every_block_sends_the_same_prompt_shapes(mix):
    m = DOCS if mix == "tiny-docs" else traffic.load_mix(mix)
    s = traffic.Stream(m, 9, 1000, 16)
    for b in range(3):
        got = sorted((s.doc_len(r.doc), r.prompt_len - s.doc_len(r.doc))
                     for r in s.block(b))
        assert sorted(set(got)) == s.prompt_shapes()
        if traffic.documents(m):
            # the same (document, own length) pairs in every block
            assert got == sorted(zip(map(s.doc_len, s.doc_of), s.own))
