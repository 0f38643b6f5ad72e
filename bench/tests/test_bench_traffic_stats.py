"""Traffic is reproducible from the seed; percentiles follow the rule of
five samples beyond."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import stats  # noqa: E402
import weights  # noqa: E402
from traffic import generator  # noqa: E402

MIX = {"domains": 16, "zipf_s": 1.1, "forget_set": 8, "forget_len": 16,
       "prompt_len": 32}
CELL = {"generate_rate": 9.0, "forget_rate": 2.5}


def test_schedule_is_reproducible_from_the_seed():
    a = generator.schedule(dict(MIX, **CELL), 2**33 + 5, 35.0)
    b = generator.schedule(dict(MIX, **CELL), 2**33 + 5, 35.0)
    c = generator.schedule(dict(MIX, **CELL), 7, 35.0)
    assert a == b
    assert a != c


def test_every_seed_gets_the_same_arrivals_in_another_order():
    a = generator.schedule(dict(MIX, **CELL), 1, 35.0)
    b = generator.schedule(dict(MIX, **CELL), 3_000_000_000, 35.0)
    for key, rate in (("generate", 9.0), ("forget", 2.5)):
        ta = np.array([t for t, _ in a[key]])
        tb = np.array([t for t, _ in b[key]])
        assert len(ta) == len(tb) == round(rate * 35.0)
        assert np.all(np.diff(ta) >= 0) and ta[0] >= 0 and ta[-1] < 35.0
        # the n-1 gaps between arrivals come from one set of n gaps: the
        # two seeds differ at most in the one gap that falls outside
        da = set(np.round(np.diff(ta), 9))
        db = set(np.round(np.diff(tb), 9))
        assert len(da ^ db) <= 2
    assert all(0 <= d < 16 for _, d in a["forget"])


def test_even_arrivals_are_one_period_apart_at_a_seeded_phase():
    cell = dict(MIX, **CELL, forget_arrivals="even")
    a = generator.schedule(cell, 11, 35.0)["forget"]
    b = generator.schedule(cell, 2**40 + 11, 35.0)["forget"]
    for s in (a, b):
        t = np.array([x for x, _ in s])
        assert len(t) == round(2.5 * 35.0)
        assert np.allclose(np.diff(t), 35.0 / len(t))
        assert 0 <= t[0] < 35.0 / len(t) and t[-1] < 35.0
    assert a != b and a == generator.schedule(cell, 11, 35.0)["forget"]
    assert generator.schedule(cell, 11, 35.0)["generate"] == \
        generator.schedule(dict(MIX, **CELL), 11, 35.0)["generate"]


def test_zipf_prefers_low_ranks():
    d = generator.zipf(20000, 16, 1.1, np.random.default_rng(0))
    counts = np.bincount(d, minlength=16)
    assert counts[0] > counts[1] > counts[4] > counts[15]


def test_data_is_reproducible_from_the_seed():
    cfg = {"vocab_size": 256}
    t1, l1 = weights.make_domains(cfg, MIX, 2**40 + 1)
    t2, l2 = weights.make_domains(cfg, MIX, 2**40 + 1)
    assert (t1 == t2).all() and (l1 == l2).all()
    assert t1.shape == (16 * 8, 17) and np.bincount(l1).tolist() == [8] * 16
    span = 256 // 16
    assert ((t1 // span) == l1[:, None]).all()
    p1 = weights.make_prompts(cfg, 5, 32, 9)
    assert (p1 == weights.make_prompts(cfg, 5, 32, 9)).all()
    assert not (p1 == weights.make_prompts(cfg, 5, 32, 10)).all()


@pytest.mark.parametrize("n,q,beyond", [(200, 0.95, 10), (199, 0.95, 9),
                                        (10000, 0.999, 10), (100, 0.9, 10),
                                        (99, 0.9, 9)])
def test_samples_beyond(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond


def test_percentile_needs_ten_samples_beyond():
    """The rule is ``stats.BEYOND`` samples beyond (five)."""
    vals = list(range(1, 201))
    assert stats.percentile(vals, 0.95) == 190
    assert stats.percentile(vals[:100], 0.95) == 95
    with pytest.raises(ValueError, match="at least 5"):
        stats.percentile(vals[:99], 0.95)
    with pytest.raises(ValueError):
        stats.percentile(vals, 1.0)
    assert stats.percentile(list(range(100, 0, -1)), 0.9) == 90
    assert stats.median([3, 1, 2]) == 2 and stats.median([4, 1, 3, 2]) == 2.5
