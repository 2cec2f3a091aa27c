"""Seeded inputs: accepted texts, fast generation, the same work for every seed."""

import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

from harness.reference import Reference
from harness.textgen import Pool, Template, mix_sizes, poisson_arrivals, rng_of

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
CONFIG_NAMES = sorted(p.stem for p in CONFIGS.glob("*.json"))


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIG_NAMES)
def test_generated_texts_are_accepted(name):
    conf = config(name)
    tmpl, ref = Template(conf["text"]), Reference(conf["regex"])
    pool = Pool(tmpl, rng_of(2**31 + 99, 0), 3000)
    texts = [pool.take(rng_of(7, i)) for i in range(4)]
    texts += tmpl.texts(rng_of(8, 0), [64, 200, 1000, 4096])
    for t in texts:
        assert ref.packed_columns(t)[-1].any(), t[:80]


def test_traffic_text_is_whole_records_of_the_pattern():
    conf = config("traffic_log")
    [text] = Template(conf["text"]).texts(rng_of(1, 0), [200_000])
    assert 200_000 - 21 < len(text) <= 200_000
    assert re.fullmatch(rb"((GET|POST|PUT) /([a-z0-9]|/)* ([0-9]{3}) (ok|err|-)\n)+", text)


# an e(125) text, (a|b)*a(a|b){125}: uniform a/b records, then a suffix of a
# fixed shape
EK125_TEXT = {"record": [{"one_of": ["a", "b"]}],
              "suffix": [{"one_of": ["a"]}, {"one_of": ["a", "b"], "repeat": [125, 125]}]}


def test_ek125_text_has_its_a_126_from_the_end():
    pool = Pool(Template(EK125_TEXT), rng_of(3, 0), 1_048_576)
    ref = Reference("(a|b)*a(a|b){125}")
    assert ref.packed_columns(pool.take(rng_of(3, 9))[-4096:])[-1].any()
    for i in range(3):
        t = pool.take(rng_of(3, 10 + i))
        assert len(t) == 1_048_576 and t[-126:-125] == b"a" and set(t) == {97, 98}


def test_two_megabytes_in_well_under_the_old_generator():
    tmpl = Template(config("traffic_log")["text"])
    t0 = time.perf_counter()
    Pool(tmpl, rng_of(5, 0), 2_000_000).take(rng_of(5, 1))
    assert time.perf_counter() - t0 < 3.0      # the recursive sampler took 16.6 s


def test_pool_texts_are_distinct_and_seeded():
    tmpl = Template(config("traffic_log")["text"])
    a = Pool(tmpl, rng_of(9, 0), 50_000)
    b = Pool(tmpl, rng_of(9, 0), 50_000)
    ta = [a.take(rng_of(9, 1 + i)) for i in range(5)]
    tb = [b.take(rng_of(9, 1 + i)) for i in range(5)]
    assert ta == tb and len(set(ta)) == 5
    assert all(50_000 - 21 < len(t) <= 50_000 for t in ta)
    assert Pool(tmpl, rng_of(10, 0), 50_000).stream != a.stream


IMIX = ([64, 594, 1518], [7, 4, 1])


def test_request_texts_fit_their_sizes():
    tmpl = Template(config("traffic_log")["text"])
    sizes = mix_sizes(500, *IMIX, rng_of(4, 0))
    texts = tmpl.texts(rng_of(4, 1), sizes)
    assert all(s - 22 <= len(t) <= s for t, s in zip(texts, sizes))
    assert min(len(t) for t in texts) > 40 and max(len(t) for t in texts) <= 1518


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a = mix_sizes(1200, *IMIX, rng_of(1, 0))
    b = mix_sizes(1200, *IMIX, rng_of(2, 0))
    assert not (a == b).all() and sorted(a) == sorted(b)
    assert [int((a == s).sum()) for s in IMIX[0]] == [700, 400, 100]
    da, db = poisson_arrivals(1000, 200.0, rng_of(1, 1)), poisson_arrivals(1000, 200.0, rng_of(2, 1))
    assert da[0] == 0 and (np.diff(da) > 0).all()
    assert sorted(np.diff(da)) != sorted(np.diff(db))   # the last gap is left out
    ga, gb = np.sort(np.diff(da)), np.sort(np.diff(db))
    assert np.isclose(ga.mean(), 1 / 200, rtol=0.02) and np.isclose(gb.mean(), 1 / 200, rtol=0.02)
    assert da[-1] < 5.0                          # all due inside the 5 s window


def test_large_and_negative_seeds():
    for seed in (2**31 + 12345, 2**40, -7):
        assert rng_of(seed, 0).integers(0, 10) in range(10)


def test_size_mix_keeps_its_weights_at_any_count():
    for n in (1, 7, 12, 1001):
        sizes = mix_sizes(n, *IMIX, rng_of(n, 0))
        counts = [int((sizes == s).sum()) for s in IMIX[0]]
        assert sum(counts) == n
        assert all(abs(c - n * w / 12) <= 1 for c, w in zip(counts, IMIX[1]))
    with pytest.raises(ValueError):
        mix_sizes(10, [64, 594], [1], rng_of(0, 0))
