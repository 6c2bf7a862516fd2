"""The control: the reference, one precision down, in the program's
place, has to come out not correct; the float64 reference against
itself reads 0."""
import numpy as np
import pytest

from bench import control, corpus, reference, run, traffic
from bench.tests import tiny


@pytest.mark.parametrize("name", ["paper-1m.table7"])
def test_bfloat16_control_fails_the_limit(name):
    _, config, mix, _ = tiny.cell(name)
    got = control.read(config, dict(mix, sample=64), tiny.SEED)
    # judged by the same verdict a run's answers get
    assert got["correct"] is False
    assert got["score_gap"] > 10 * config["limits"]["score_gap"]
    c = corpus.generate(config, tiny.SEED)
    qs = traffic.queries(mix, c.df(), c.token_counts(), c.num_docs, 8,
                         tiny.SEED)
    exact = run.load_ranking(config["ranking"]).Reference(
        c, {int(t) for q in qs for t in q})
    k = int(config["k"])
    itself = [(q, *exact.answer(q, k).served(k)) for q in qs]
    assert reference.compare(itself, exact, k) == {"score_gap": 0.0,
                                                   "bad_answers": 0}


@pytest.mark.parametrize("found,unanswered,correct", [
    ({"score_gap": 2e-7, "bad_answers": 0}, 0, True),
    ({"score_gap": 2e-5, "bad_answers": 0}, 0, False),
    ({"score_gap": 0.0, "bad_answers": 1}, 0, False),
    ({"score_gap": 0.0, "bad_answers": 0}, 1, False),
])
def test_verdict_holds_every_number_to_its_limit(found, unanswered,
                                                 correct):
    checks, ok = reference.verdict(found, unanswered, {"score_gap": 1e-5})
    assert ok is correct
    assert list(checks) == ["score_gap", "bad_answers", "unanswered"]
    assert all(set(c) == {"value", "limit"} for c in checks.values())


def test_gap_names_answers_wrong_in_kind():
    want = reference.Answer(ids=np.array([3, 1]), top=np.array([0.9, 0.5]),
                            score=np.array([0, 0.5, 0, 0.9]))
    assert reference.gap([3, 1, -1], [0.9, 0.5, 0], want) == 0.0
    assert reference.gap([3, -1, -1], [0.9, 0, 0], want) is None
    assert reference.gap([3, 3, -1], [0.9, 0.9, 0], want) is None
    assert reference.gap([3, 7, -1], [0.9, 0.5, 0], want) is None
    # a swapped-in doc whose score is not the reference's
    assert reference.gap([3, 2, -1], [0.9, 0.5, 0], want) == \
        pytest.approx(0.5 / 0.9)
    # ties may come in either order
    tie = reference.Answer(ids=np.array([1, 2]), top=np.array([0.5, 0.5]),
                           score=np.array([0, 0.5, 0.5]))
    assert reference.gap([2, 1], [0.5, 0.5], tie) == 0.0
