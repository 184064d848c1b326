import numpy as np
import pytest

from bench import traffic

SPEC = {"prompt": {"median": 128, "min": 32, "max": 512},
        "output": {"median": 384, "min": 128, "max": 1024}}


def test_same_seed_same_requests():
    a = traffic.requests(SPEC, 49155, 2**40 + 3, n=100)
    b = traffic.requests(SPEC, 49155, 2**40 + 3, n=100)
    assert [r["max_new_tokens"] for r in a] == [r["max_new_tokens"] for r in b]
    assert all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b))


def test_other_seed_other_order_same_work():
    a = traffic.requests(SPEC, 49155, 1, n=100)
    b = traffic.requests(SPEC, 49155, 2, n=100)
    assert [r["tokens"].size for r in a] != [r["tokens"].size for r in b]
    B = traffic.BLOCK
    for i in range(0, 100 - B + 1, B):  # every whole block asks for the same work
        pa = sorted((r["tokens"].size, r["max_new_tokens"]) for r in a[i:i + B])
        pb = sorted((r["tokens"].size, r["max_new_tokens"]) for r in b[i:i + B])
        assert pa == pb


@pytest.mark.parametrize("part", ["prompt", "output"])
def test_lengths_follow_the_cut_lognormal(part):
    lens = traffic.quantile_lengths(SPEC[part], 32)
    d = SPEC[part]
    assert lens.min() >= d["min"] and lens.max() <= d["max"]
    assert abs(np.median(lens) - d["median"]) / d["median"] < 0.1
    assert list(lens) == sorted(lens)


def test_tokens_in_vocab():
    for r in traffic.requests(SPEC, 1000, 5, n=100):
        assert r["tokens"].dtype == np.int32
        assert 0 <= r["tokens"].min() and r["tokens"].max() < 1000


def test_chunk_lengths_cover_every_remainder():
    assert traffic.chunk_lengths(SPEC, 64) == list(range(1, 65))
    assert traffic.chunk_lengths({"prompt": {"min": 5, "max": 7}}, 64) == [5, 6, 7]
    assert traffic.chunk_lengths({"prompt": {"min": 64, "max": 66}}, 64) == [1, 2, 64]


def test_lengths_are_never_rounded():
    lens = traffic.quantile_lengths(SPEC["prompt"], traffic.BLOCK)
    assert len(set(lens)) == traffic.BLOCK and any(n % 16 for n in lens)


def test_a_fixed_length_mix():
    spec = {"prompt": {"median": 128, "min": 128, "max": 128},
            "output": {"median": 128, "min": 128, "max": 128}}
    reqs = traffic.requests(spec, 32000, 2**40 + 5, n=50)
    assert {(r["tokens"].size, r["max_new_tokens"]) for r in reqs} == {(128, 128)}
    assert traffic.chunk_lengths(spec, 64) == [64]
