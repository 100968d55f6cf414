"""The traffic the benchmark owns (``bench/data.py``)."""

import numpy as np

from bench.data import CtrStream


def test_rows_do_not_depend_on_labels():
    s = CtrStream([1000, 40_000_000, 3], 13, 512, 1.05, 2 ** 31 + 5)
    a, b = s.batch_at(7), s.batch_at(7, labels=False)
    for k in ("dense", "sparse"):
        np.testing.assert_array_equal(a[k], b[k])
    assert "label" not in b and set(np.unique(a["label"])) <= {0, 1}
    assert (a["sparse"] >= 0).all() and (a["sparse"] < [1000, 40_000_000, 3]).all()
    assert not np.array_equal(s.batch_at(8)["sparse"], a["sparse"])
