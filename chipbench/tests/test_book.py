"""The serving run's book: numbers per request, and a sample of answers
that depends on which requests were answered, not on the order."""
import types

import numpy as np

from chipbench import serving


def _answer(book, j, n_cand, ok=True):
    sel = types.SimpleNamespace(n_candidates=n_cand)
    resp = types.SimpleNamespace(ok=ok, result=types.SimpleNamespace(
        selection=sel))
    book.answer(j, types.SimpleNamespace(result=lambda: resp))


def _filled(order, seed=2**40 + 3):
    book = serving.Book(64, 5, seed)
    for i in range(40):
        book.add(i, 1000 + i, float(i))
    for j in order:
        _answer(book, j, n_cand=7 if j == 33 else 1, ok=j != 4)
    return book


def test_sample_ignores_answer_order():
    order = list(range(40))
    a = _filled(order).sample()
    b = _filled(order[::-1]).sample()
    assert [(x.row, x.seed) for x in a] == [(x.row, x.seed) for x in b]
    assert len(a) == 5 and 33 in [x.row for x in a]       # most candidates
    assert 4 not in [x.row for x in a]                    # not DONE
    assert all(x.seed == 1000 + x.row for x in a)


def test_sample_is_drawn_from_the_seed():
    rows = lambda seed: [x.row for x in _filled(range(40), seed).sample()]
    assert rows(1) == rows(1)
    assert rows(1) != rows(2)


def test_waiting_and_states():
    book = serving.Book(8, 2, 1)
    for i in range(3):
        book.add(i, i, 0.0)
    _answer(book, 0, 1)
    assert book.waiting()
    _answer(book, 1, 1, ok=False)
    _answer(book, 2, 1)
    assert not book.waiting()
    assert list(book.state[:3]) == [1, 0, 1]
    assert np.all(book.t_done[:3] > 0)
