"""The generators give the same bytes for the same seed, other bytes for
another, and the same sizes to every seed."""

import math
from collections import Counter

import numpy as np

from chipbench import harness
from chipbench.traffic import conversations as conv
from chipbench.traffic.text_writer import TextWriter

MIX = harness.load_json(harness.HERE / "traffic" / "chat_steady.json")
WC = harness.load_json(harness.HERE / "configs" / "wordcount_hibench.json")
BIG = 2**31 + 12345


def _bytes(cs):
    return b"".join(np.float64(c.due).tobytes() + c.prompt.tobytes()
                    + np.int64(c.out_tokens).tobytes() for c in cs)


def test_conversations_follow_the_seed():
    a = conv.conversations(MIX, BIG, 25, 151936)
    assert _bytes(a) == _bytes(conv.conversations(MIX, BIG, 25, 151936))
    assert _bytes(a) != _bytes(conv.conversations(MIX, BIG + 1, 25, 151936))


def test_every_seed_offers_the_same_sizes():
    r = MIX["round"]
    for seed in (1, BIG):
        cs = conv.conversations(MIX, seed, r, 151936)
        lengths = sorted(len(c.prompt) for c in cs)
        weights = MIX["prompt_tokens"]["weights"]
        assert lengths == sorted(conv.round_sizes(MIX)["prompt"])
        for length, w in zip(MIX["prompt_tokens"]["lengths"], weights):
            assert abs(lengths.count(length) - w * r) < 1
        sizes = conv.round_sizes(MIX)
        assert sorted(c.out_tokens for c in cs) == sorted(sizes["output"])
        gaps = np.diff([0.0] + [c.due for c in cs])
        assert np.allclose(sorted(gaps), sorted(sizes["gap"]))
    outs = conv.round_sizes(MIX)["output"]
    assert min(outs) >= 8 and max(outs) <= 128
    assert np.median(outs) == 32


def test_text_follows_the_seed_and_the_record_format():
    cfg = dict(WC, datasize=200_000)
    gen = TextWriter(cfg)
    a, counts = gen.job(BIG, 0)
    assert a == gen.job(BIG, 0)[0]
    assert a != gen.job(BIG + 1, 0)[0] and a != gen.job(BIG, 1)[0]
    longest = (WC["key_words"][1] + WC["value_words"][1]) * (WC["word_letters"][1] + 1)
    assert cfg["datasize"] - longest < len(a) <= cfg["datasize"]
    assert a.endswith(b"\n")
    for line in a.split(b"\n")[:-1]:
        key, value = line.split(b"\t")
        assert WC["key_words"][0] <= len(key.split(b" ")) < WC["key_words"][1]
        assert WC["value_words"][0] <= len(value.split(b" ")) < WC["value_words"][1]
    seen = Counter(a.split())
    assert set(seen) <= set(gen.words) and len(gen.words) == WC["list_words"]
    assert [seen[w] for w in gen.words] == counts.tolist()


def test_a_job_splits_into_the_sources_number_of_blocks():
    src = math.ceil(WC["source_datasize"] / WC["source_block_size"])
    assert math.ceil(WC["datasize"] / WC["block_size"]) == src == 3
    assert WC["source_datasize"] / WC["datasize"] == 10
    assert abs(WC["source_block_size"] / WC["block_size"] - 10) < 1e-6
