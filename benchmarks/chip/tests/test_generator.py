"""The traffic generator: the same requests from the same seed, the same
sizes in another order from another seed, and the published means."""
import numpy as np
import pytest

import generator

MIXES = ["closed-sharegpt"]
SEEDS = (7, 2 ** 33 + 5)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = generator.load_mix(name)
    a = generator.generate(mix, SEEDS[1], 64000)
    b = generator.generate(mix, SEEDS[1], 64000)
    assert len(a) == len(b) == mix["clients"] * mix["rounds"]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.client, x.resumed) == \
            (y.max_new_tokens, y.client, y.resumed)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_sizes_other_order(name):
    mix = generator.load_mix(name)
    a = generator.generate(mix, SEEDS[0], 64000)
    b = generator.generate(mix, SEEDS[1], 64000)
    for x, y in ((a, b), (a[:mix["clients"]], b[:mix["clients"]])):
        assert sorted(len(s.prompt) for s in x) == \
            sorted(len(s.prompt) for s in y)
        assert sorted((s.max_new_tokens, s.resumed) for s in x) == \
            sorted((s.max_new_tokens, s.resumed) for s in y)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert [s.client for s in a] == [i % mix["clients"] for i in range(len(a))]


@pytest.mark.parametrize("which", ["prompt", "output"])
def test_lengths_have_the_published_mean(which):
    """The clipped lognormal's mean is the source's mean, and later rounds
    (fresh requests) read it."""
    mix = generator.load_mix("closed-sharegpt")
    dist = mix[which]
    sizes = generator._sizes(dist, 4096)
    assert abs(sizes.mean() / dist["mean"] - 1) < 0.005
    assert sizes.min() >= dist["lo"] and sizes.max() <= dist["hi"]


def test_stationary_start_is_length_biased():
    """Clients caught mid-flight sit in longer requests than a fresh draw
    gives, each part-way through, and the rest of it still to come."""
    mix = generator.load_mix("closed-sharegpt")
    p, o, a = generator.stationary_first_round(mix)
    fresh = generator._sizes(mix["output"], mix["clients"])
    assert o.mean() > 1.5 * fresh.mean()
    assert (a >= 0).all() and (a < o).all()
    assert 0.4 < (a / o).mean() < 0.6
    specs = generator.generate(mix, SEEDS[0], 64000)[:mix["clients"]]
    for s in specs:
        assert s.max_new_tokens >= 1
        assert len(s.prompt) - s.resumed in set(p.tolist())


def test_every_request_fits_the_engine():
    """Under the engine's left padding (a prompt fills its power-of-two
    bucket), every request's last position is inside ``max_seq``."""
    mix = generator.load_mix("closed-sharegpt")
    max_seq = mix["engine"]["max_seq"]
    for s in generator.generate(mix, SEEDS[1], 64000):
        last = generator.bucket_of(len(s.prompt), max_seq) \
            + s.max_new_tokens - 1
        assert len(s.prompt) < max_seq and last <= max_seq - 1


def test_buckets_reached():
    mix = generator.load_mix("closed-sharegpt")
    assert generator.buckets_of(mix) == [16, 32, 64, 128, 256, 512, 1024,
                                         2048]
    for s in generator.generate(mix, SEEDS[0], 64000):
        assert generator.bucket_of(len(s.prompt), 2304) in \
            generator.buckets_of(mix)
