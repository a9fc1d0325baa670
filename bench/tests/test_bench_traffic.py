"""The traffic generator: reproducible, in range, labelled truthfully."""
import pytest

import tiny  # noqa: F401  (sets sys.path)
import arrivals as A
from repro.core.privacy import PrivacyDetector

MIXES = ["chat", "code"]


def _mix(name):
    return A.load_named(tiny.BENCH, name)


def _requests(mix, seed):
    return A.open_segment(mix, seed, 1, 10.0, 40.0, 0)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a, b = _requests(mix, 2 ** 33 + 7), _requests(mix, 2 ** 33 + 7)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]
    c = _requests(mix, 2 ** 33 + 8)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range(name):
    mix = _mix(name)
    pt, ot = mix["prompt_tokens"], mix["output_tokens"]
    for seed in (1, 2, 3):
        for r in _requests(mix, seed):
            assert pt["min"] <= r.prompt_tokens <= pt["max"]
            assert len(A.token_ids(r.prompt)) == r.prompt_tokens
            assert ot["min"] <= r.max_new <= ot["max"]
            assert r.prompt.isascii()


@pytest.mark.parametrize("name", MIXES)
def test_open_loop_offers_the_rate(name):
    mix = _mix(name)
    reqs = _requests(mix, 11)
    assert len(reqs) == round(mix["rate_per_s"] * 40.0)
    assert all(10.0 <= r.due < 50.0 for r in reqs)
    assert sorted(r.due for r in reqs) == [r.due for r in reqs]
    # every seed offers the same schedule; only the text differs
    other = _requests(mix, 12)
    assert [(r.due, r.prompt_tokens, r.max_new, r.private) for r in reqs] \
        == [(r.due, r.prompt_tokens, r.max_new, r.private) for r in other]
    assert [r.prompt for r in reqs] != [r.prompt for r in other]


def test_schedule_seed_changes_the_order():
    mix = _mix("chat")
    a = _requests(mix, 1)
    b = _requests(dict(mix, schedule_seed=2), 1)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    assert sorted(r.prompt_tokens for r in a) == \
        sorted(r.prompt_tokens for r in b)


@pytest.mark.parametrize("name", MIXES)
def test_private_labels_match_the_detector(name):
    det = PrivacyDetector()
    mix = _mix(name)
    n_priv = 0
    for seed in list(range(6)) + [2 ** 31 + 11, 2 ** 33 + 5]:
        for r in _requests(mix, seed):
            assert det.detect(r.prompt) == r.private, r.prompt[:80]
            n_priv += r.private
    assert (n_priv > 0) == (mix.get("private_share", 0.5) > 0)


def test_short_prompts_keep_the_private_phrase():
    det = PrivacyDetector()
    import numpy as np
    rng = np.random.default_rng(0)
    for _ in range(200):
        t = A.prompt_text(16, True, rng)
        assert len(A.token_ids(t)) == 16 and det.detect(t)
        assert not det.detect(A.prompt_text(16, False, rng))
