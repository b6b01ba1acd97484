"""The scoring record counts every answer's id and keeps whole batches,
a seed-drawn sixteenth of the pumps, for the check."""
from __future__ import annotations

import types

import numpy as np

import bench_testkit  # noqa: F401  (puts the checkout on the path)

from bench.serving import Record  # noqa: E402


def _pump(lo, n):
    return [types.SimpleNamespace(request_id=lo + i,
                                  probs=np.full(3, lo + i, np.float32),
                                  score=float(lo + i)) for i in range(n)]


def test_record_counts_ids_and_samples_whole_pumps():
    rec = Record(salt=2147483650)          # a large seed's salt
    for p in range(40):
        rec.add(_pump(100 * p, 8))
    assert rec.ids == [100 * p + i for p in range(40) for i in range(8)]
    ids, probs, scores = rec.sample()
    pumps = sorted({int(i) // 100 for i in ids})
    assert pumps == [p for p in range(40) if p % 16 == 2147483650 % 16]
    assert len(ids) == 8 * len(pumps)
    np.testing.assert_array_equal(probs[:, 0], ids.astype(np.float32))
    np.testing.assert_array_equal(scores, ids.astype(np.float32))
