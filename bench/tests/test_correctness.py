"""What decides `correct`: the control (the reference in bfloat16, put in
the program's place) and a broken timed path both come out as not correct;
the sound program does not."""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest


def _limits(root):
    return json.loads((root / "bench/configs/tiny.json").read_text())["limits"]


def test_control_fails(tiny_root):
    import harness

    cell = harness.Cell.load(tiny_root, "sift1m.batch", trace=False)
    batcher, pool, proj = harness.build(cell, 5)
    rec = harness.window(cell, batcher, pool, 1, 5, None,
                         harness.CompileCounter())
    nums = harness.reference_phase(cell, 5, proj, pool, rec, set(),
                                   ("bfloat16",))
    limits = _limits(tiny_root)
    assert all(nums[n] <= limits[n] for n in limits), nums
    ctl = rec["controls"]["bfloat16"]
    assert any(ctl[n] > limits[n] for n in limits), ctl
    # each number separates the control from the program
    for n in limits:
        assert ctl[n] > nums[n], (n, ctl, nums)


def _broken(monkeypatch, alter):
    """Break the timed path underneath: every search result the queue gets
    from the searcher is altered where it is produced."""
    from repro.core import engine

    search = engine.ActiveSearcher.search

    def bad(self, queries, k, mode="refined"):
        return alter(search(self, queries, k, mode))

    monkeypatch.setattr(engine.ActiveSearcher, "search", bad)


@pytest.mark.parametrize("fault", ["answer_id", "answer_dist", "loop_stat"])
def test_broken_answer_fails(tiny_root, run_cell, monkeypatch, fault):
    def alter(res):
        if fault == "answer_id":  # one id swapped for another point's
            return res._replace(ids=res.ids.at[0, 0].set(res.ids[0, 0] + 1))
        if fault == "answer_dist":
            return res._replace(dists=res.dists * 1.001)
        return res._replace(iters=res.iters + 1)

    _broken(monkeypatch, alter)
    out = run_cell(tiny_root, "sift1m.batch")
    assert out["correct"] is False, out["checks"]
