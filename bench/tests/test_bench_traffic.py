"""The traffic copy: the same work for every seed, in another order."""
import numpy as np
import pytest

import traffic


@pytest.fixture(scope="module")
def mix():
    return traffic.load_mix("mixed")


def test_same_seed_same_schedule(mix):
    a = traffic.generate(mix, 2 ** 33 + 5, 4096, 64000)
    b = traffic.generate(mix, 2 ** 33 + 5, 4096, 64000)
    assert [(x.due, x.max_new, x.cls) for x in a] == \
        [(x.due, x.max_new, x.cls) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_sizes_and_gaps_not_order(mix):
    a = traffic.generate(mix, 1, 4096, 64000)
    b = traffic.generate(mix, 2, 4096, 64000)
    # documents are clipped to context - output, which depends on the
    # pairing; the chat prompts are not
    short = lambda s: sorted(len(x.prompt) for x in s if x.cls == "short")
    assert short(a) == short(b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    gaps = lambda s: np.diff([0.0] + [x.due for x in s])
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(b)))
    assert [x.cls for x in a] != [x.cls for x in b]
    assert abs(a[-1].due - b[-1].due) < 1e-6 * a[-1].due


def test_every_block_holds_each_class_in_its_share(mix):
    s = traffic.generate(mix, 9, 4096, 64000)
    block = mix["block"]
    for i in range(0, len(s), block):
        cls = [x.cls for x in s[i:i + block]]
        assert cls.count("short") == cls.count("long") == block // 2


def test_medians_follow_the_distributions(mix):
    s = traffic.generate(mix, 3, 10 ** 6, 64000)
    short = [x for x in s if x.cls == "short"]
    long_ = [x for x in s if x.cls == "long"]
    # the short prompts' mean is 83; the documents' median 41,417
    assert np.mean([len(x.prompt) for x in short]) == pytest.approx(83, rel=0.15)
    assert np.median([len(x.prompt) for x in long_]) == pytest.approx(41417,
                                                                      rel=0.05)
    assert np.median([x.max_new for x in short]) == pytest.approx(300, rel=0.05)
    assert np.median([x.max_new for x in long_]) == pytest.approx(350, rel=0.05)


def test_prompt_clipped_to_context_minus_output(mix):
    s = traffic.generate(mix, 4, 4096, 64000)
    lo, hi = mix["output_clip"]
    for x in s:
        assert lo <= x.max_new <= hi
        assert len(x.prompt) + x.max_new <= 4096
        assert x.max_new > 1
    assert max(len(x.prompt) for x in s) > 3000


def test_token_ids_in_vocab_and_offline_queue_at_zero(mix):
    off = dict(mix, mode="offline")
    s = traffic.generate(off, 5, 4096, 1000)
    assert all(x.due == 0.0 for x in s)
    assert all(x.prompt.min() >= 0 and x.prompt.max() < 1000 for x in s)


def test_shared_prefixes_and_bursts(mix):
    m = dict(mix, prefix={"groups": 2, "tokens": 16, "zipf": 1.5},
             bursts={"factor": 4, "every_s": 10, "duration_s": 3})
    s = traffic.generate(m, 6, 4096, 64000)
    heads = {tuple(x.prompt[:16]) for x in s if len(x.prompt) > 16}
    assert len(heads) <= 2
    assert s[-1].due < traffic.generate(mix, 6, 4096, 64000)[-1].due


def test_sessions_are_refused(mix):
    with pytest.raises(ValueError):
        traffic.generate(dict(mix, sessions={"turns": 3}), 1, 4096, 64000)
