"""CPU tests of the per-layer metrics of ``text768.mixed``: the ADC's and
the symmetric-PQ lookups' device time from the scope split, and the
vectors the CASR rerank read from the search counters."""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run  # noqa: E402

ADC = "search_many.adc_ms_per_query"
SYM = "insert_many.sym_ms_per_insert"
RERANK = "search.rerank_vectors_per_query"


def record(search=(64, 64), inserts=(16, 16), vectors_read=0):
    search_ctr = {"useful_vec_bytes_read": 0, "wasted_vec_bytes_read": 0}
    if vectors_read is not None:
        search_ctr["vectors_read"] = vectors_read
    return {"cell": "text768.mixed",
            "search_waves": [{"n": n, "wall_s": 1.0, "rounds": []}
                             for n in search],
            "insert_waves": [{"n": n, "wall_s": 0.1, "dropped": 0}
                             for n in inserts],
            "widths": {"dim": 768, "r": 48, "pq_m": 96, "beam_width": 4},
            "counters": {"search": search_ctr, "insert": {}}}


SCOPES = {
    "_search_many": {
        "navis.traverse": 2.0,
        "navis.traverse/navis.score": 0.9,
        "navis.traverse/navis.score/navis.adc": 0.25,
        "navis.entrance": 0.5,
        "navis.entrance/navis.adc": 0.07,
        "navis.cache_replay": 1.0,
        "(unscoped)": 0.1},
    "_insert_many": {
        "navis.seek": 0.3,
        "navis.seek/navis.select": 0.04,
        "navis.seek/navis.select/navis.sym": 0.01,
        "navis.seek/navis.entrance/navis.adc": 0.02,
        "navis.commit": 0.2,
        "navis.commit/navis.link": 0.15,
        "navis.commit/navis.link/navis.sym": 0.06,
        "navis.commit/navis.entrance_update/navis.sym": 0.005},
}


def test_adc_sums_every_path_that_ends_in_it():
    """traverse's and entrance's ADC, 0.32 s over 128 queries; the
    insert program's ADC is not the search program's."""
    got = run.load_reader("layer_metrics", ADC)(record(),
                                                {"scopes": SCOPES})
    assert got == pytest.approx((0.25 + 0.07) * 1e3 / 128)


def test_sym_sums_the_commit_and_the_seek():
    got = run.load_reader("layer_metrics", SYM)(record(),
                                                {"scopes": SCOPES})
    assert got == pytest.approx((0.01 + 0.06 + 0.005) * 1e3 / 32)


@pytest.mark.parametrize("name", [ADC, SYM])
def test_scope_readers_report_nothing_without_their_scope(name):
    read = run.load_reader("layer_metrics", name)
    # an untraced run
    assert read(record(), None) is None
    # a program traced before the scopes were named
    bare = {"scopes": {p: {"(unscoped)": 1.0} for p in SCOPES}}
    assert read(record(), bare) is None
    # no waves of the program traced
    assert read(record(search=(), inserts=()), {"scopes": SCOPES}) is None


def test_rerank_vectors_per_query():
    """320 vectors read over 64 queries."""
    read = run.load_reader("layer_metrics", RERANK)
    assert read(record(search=(64,), vectors_read=320),
                {"scopes": SCOPES}) == pytest.approx(320 / 64)
    assert read(record(search=(), vectors_read=3), None) is None
    # a program without the counter reports nothing
    assert read(record(vectors_read=None), None) is None


def test_rerank_vectors_past_the_byte_counters_wrap():
    """1,200 3 KiB vectors a query over 1,280 queries is 4.7e9 bytes, past
    the 2**32 at which the int32 byte counters wrap; the vector count
    is read whole."""
    read = run.load_reader("layer_metrics", RERANK)
    rec = record(search=(64,) * 20, vectors_read=1200 * 1280)
    assert 1200 * 1280 * 768 * 4 > 2 ** 32
    assert read(rec, None) == pytest.approx(1200.0)
