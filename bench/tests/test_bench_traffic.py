"""The traffic generator reproduces from the seed, and every seed asks
for the same work."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import traffic as tr  # noqa: E402

BIG = 2**31 + 12345


def test_image_pool_reproduces_from_the_seed():
    t = {"kind": "image_pool", "batch": 4, "pool": 2, "point": "r8w4"}
    a, b = tr.image_pool(t, BIG), tr.image_pool(t, BIG)
    c = tr.image_pool(t, BIG + 1)
    assert a.shape == (2, 4, 28, 28, 1)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert float(a.min()) >= 0.0 and float(a.max()) <= 1.0
    # the two batches of the pool differ
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))


def test_seeds_beyond_32_bits_stay_distinct():
    a = np.asarray(tr.key_of(5))
    b = np.asarray(tr.key_of(5 + 2**32))
    assert not np.array_equal(a, b)


def test_budgets_same_multiset_for_every_seed():
    t = tr.load("chat_b64")
    base = tr.budgets(t)
    assert base.shape == (64,) and base.min() >= 1
    assert base.max() == t["output"]["cap"]
    for seed in (BIG, BIG + 1):
        for i in (0, 3):
            got = tr.batch_budgets(t, seed, i)
            assert np.array_equal(np.sort(got), base)
            assert np.array_equal(got, tr.batch_budgets(t, seed, i))
    assert not np.array_equal(tr.batch_budgets(t, BIG, 0),
                              tr.batch_budgets(t, BIG, 1))
    fixed = tr.budgets(tr.load("docs_b8_p1024"))
    assert np.array_equal(fixed, np.full(8, 8))


def test_prompts_reproduce_from_the_seed():
    t = {"kind": "static_batches", "batch": 3, "prompt_len": 5,
         "output": {"dist": "fixed", "tokens": 2}}
    a = np.asarray(tr.prompt_maker(t, BIG, 100)(2))
    assert a.shape == (3, 5) and a.dtype == np.int32
    assert np.array_equal(a, np.asarray(tr.prompt_maker(t, BIG, 100)(2)))
    assert not np.array_equal(a, np.asarray(tr.prompt_maker(t, BIG, 100)(3)))
    assert a.min() >= 0 and a.max() < 100
