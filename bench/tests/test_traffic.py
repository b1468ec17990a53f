"""The generator is deterministic per seed and matches its parameters."""
import numpy as np
import pytest

from bench import registry, traffic

P = registry.traffic("chat-steady")


def _key(reqs):
    return [(r.due, r.prompt_tokens, r.max_tokens) for r in reqs]


def test_same_seed_same_schedule():
    a = traffic.schedule(P, 10.0, [10.0, 40.0], 2 ** 31 + 77)
    b = traffic.schedule(P, 10.0, [10.0, 40.0], 2 ** 31 + 77)
    assert _key(a) == _key(b)


def test_other_seed_same_work_in_another_order():
    """Each segment holds the same sizes and intervals for every seed."""
    a = traffic.schedule(P, 10.0, [10.0, 40.0], 1)
    b = traffic.schedule(P, 10.0, [10.0, 40.0], 2)
    assert _key(a) != _key(b)
    for lo, hi in ((0.0, 10.0), (10.0, 40.0)):
        sa = [r for r in a if lo <= r.due < hi]
        sb = [r for r in b if lo <= r.due < hi]
        assert len(sa) == len(sb) == round(10.0 * (hi - lo))
        assert sorted(r.max_tokens for r in sa) == \
            sorted(r.max_tokens for r in sb)
        ga = sorted(np.diff([r.due for r in sa] + [hi]))
        gb = sorted(np.diff([r.due for r in sb] + [hi]))
        np.testing.assert_allclose(ga, gb)


@pytest.mark.parametrize("rate", [2.0, 12.5])
def test_rate_and_lengths_match_the_parameters(rate):
    span = 2000.0 / rate
    reqs = traffic.schedule(P, rate, [span], 5)
    due = np.array([r.due for r in reqs])
    assert np.all(np.diff(due) >= 0) and due[0] == 0.0 and due[-1] < span
    assert len(reqs) == 2000
    gaps = np.diff(due)
    # gamma(0.73) intervals: coefficient of variation 1/sqrt(0.73)
    assert abs(gaps.std() / gaps.mean() - 0.73 ** -0.5) < 0.15
    pl = np.array([len(r.prompt_tokens) for r in reqs])
    pr = P["prompt_tokens"]
    assert pl.min() >= pr["min"] and pl.max() <= pr["max"]
    assert abs(np.median(pl) / pr["median"] - 1) < 0.1
    out = np.array([r.max_tokens for r in reqs])
    assert out.min() >= 1 and out.max() <= P["output"]["cap"]
    assert 130 < out.mean() < 190
    assert all(len(r.prompt_tokens) + r.max_tokens <= 2048 for r in reqs)
    assert all(traffic.N_SPECIAL <= t < traffic.VOCAB
               for r in reqs[:50] for t in r.prompt_tokens)


def test_token_ids_are_the_programs():
    from repro.data.tokenizer import HashTokenizer

    tok = HashTokenizer()
    for w in traffic.HISTORY_WORDS[:40] + ["Weather", "tl;dr"]:
        assert traffic.token_id(w) == tok.token_id(w)


def test_training_stream_differs_from_runs():
    a = traffic.training_requests(P, 20, 7)
    b = traffic.schedule(P, 10.0, [60.0], 7)[:20]
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    assert all(len(r.answer_tokens) == r.max_tokens for r in a)
    assert all(r.answer_tokens[-1] == traffic.EOS_ID for r in a)
