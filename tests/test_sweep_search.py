"""The selection of ``scripts/sweep_search.py``: the cheapest setting
whose every seed holds the limit, run no further than it must be."""
from __future__ import annotations

import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "scripts" / \
    "sweep_search.py"
_spec = importlib.util.spec_from_file_location("sweep_search", _PATH)
sweep = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep)


def fake(misses):
    """``one`` over a table {(setting, seed): (search, readback)}, logging
    the calls it takes."""
    calls = []

    def one(setting, seed):
        calls.append((setting, seed))
        s, r = misses[setting, seed]
        return {"search_miss": s, "readback_miss": r,
                "search_ms_per_query": float(len(calls))}
    return one, calls


def table(rows):
    return {(setting, seed): m for setting, ms in rows.items()
            for seed, m in enumerate(ms, start=1)}


def test_takes_the_first_that_holds_every_seed():
    misses = table({"a": [(0.05, 0), (0.07, 0), (0.01, 0)],
                    "b": [(0.04, 0), (0.06, 0.01), (0.02, 0.0)],
                    "c": [(0.0, 0), (0.0, 0), (0.0, 0)]})
    one, calls = fake(misses)
    chosen, results = sweep.choose(["a", "b", "c"], [1, 2, 3], 0.06, one)
    assert chosen == "b"          # 0.06 is at the limit, not over it
    # "a" stops at its second seed; "c" is never run
    assert calls == [("a", 1), ("a", 2), ("b", 1), ("b", 2), ("b", 3)]
    s = sweep.summary(results)
    assert s["a"]["seeds"] == 2 and s["a"]["worst_search_miss"] == 0.07
    assert s["b"]["worst_readback_miss"] == 0.01


@pytest.mark.parametrize("bad", [(0.2, 0.0), (0.0, 0.2)])
def test_either_miss_rules_a_setting_out(bad):
    one, _ = fake(table({"a": [(0.0, 0.0), bad]}))
    chosen, results = sweep.choose(["a"], [1, 2], 0.06, one)
    assert chosen is None and len(results["a"]) == 2
