"""The input generator: the same seed gives the same rows, another seed
other rows of the same shapes, and seeds of any size are taken."""

import json
import pathlib

import numpy as np
import pytest
from harness import data

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def test_seed_words_take_large_seeds():
    w = data.seed_words(2 ** 40 + 3, 3)
    assert w == data.seed_words(2 ** 40 + 3, 3)
    assert w != data.seed_words(3, 3)
    assert all(0 <= x < 2 ** 31 for x in w)


@pytest.mark.parametrize("name,width,lo,hi", [("einet-pd-svhn", 3072, 0.0, 1.0),
                                              ("einet-rat", 512, -np.inf, np.inf)])
def test_same_seed_same_rows(name, width, lo, hi):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    a, b, c = data.rows(cfg, 64, 11), data.rows(cfg, 64, 11), data.rows(cfg, 64, 12)
    assert a.shape == c.shape == (64, width) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert lo <= a.min() and a.max() <= hi
    # every row differs, so no two steps of a window see the same batch
    assert len({r.tobytes() for r in a}) == len(a)
