"""The traffic generator: deterministic per seed, and true to the
parameters each mix states."""
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import traffic

SEED = 2 ** 33 + 17          # wider than 32 bits, as run seeds may be


@pytest.mark.parametrize("name", ["decode_long_16", "decode_long_32"])
def test_requests_repeat_for_a_seed(name):
    mix = traffic.load_mix(name)
    a = traffic.requests(mix, 1000, SEED, 200)
    b = traffic.requests(mix, 1000, SEED, 200)
    c = traffic.requests(mix, 1000, SEED + 1, 200)
    assert [(r.prompt, r.max_new_tokens) for r in a] == \
        [(r.prompt, r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", ["decode_long_16", "decode_long_32"])
def test_every_seed_gets_the_same_work_in_the_same_order(name):
    """Seeds change the token ids, never the sizes or their order, and
    every block holds the same multiset."""
    mix = traffic.load_mix(name)
    B = mix["block"]
    runs = [traffic.requests(mix, 1000, seed, 3 * B)
            for seed in (1, SEED)]
    assert [(len(r.prompt), r.max_new_tokens) for r in runs[0]] == \
        [(len(r.prompt), r.max_new_tokens) for r in runs[1]]
    rs = runs[1]
    for k in range(3):
        blk = rs[k * B:(k + 1) * B]
        assert sorted(len(r.prompt) for r in blk) == sorted(
            traffic.length_block(mix["prompt_len"], B).tolist())
        assert sorted(r.max_new_tokens for r in blk) == sorted(
            traffic.length_block(mix["output_len"], B).tolist())
    assert [r.max_new_tokens for r in rs[:B]] != \
        [r.max_new_tokens for r in rs[B:2 * B]]


@pytest.mark.parametrize("name", ["decode_long_16", "decode_long_32"])
def test_lengths_follow_the_stated_distributions(name):
    mix = traffic.load_mix(name)
    out = traffic.length_block(mix["output_len"], 64)
    assert out.min() == 256 and out.max() == 992
    assert abs(np.median(out) - 512) <= 20
    p = traffic.length_block(mix["prompt_len"], 64)
    assert {8: 32, 16: 32} == {v: int(np.sum(p == v)) for v in (8, 16)}
    assert max(p) + max(out) <= mix["engine"]["max_len"]


def test_unknown_loop_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text('{"loop": "open"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="loop"):
        traffic.load_mix("x")
