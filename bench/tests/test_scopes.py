"""CPU tests of the reduction of device time to the engine's named stages
(``scopes.py``) and of the per-layer metrics that read it."""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run, scopes, trace_reduce  # noqa: E402
from bench.tests import record_scopes_trace  # noqa: E402

BM = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
MS = 1_000_000
# the per-layer metrics read from the scope split: name -> (program, scope)
READERS = {
    "search_many.entrance_ms_per_query": ("_search_many", "navis.entrance"),
    "search_many.traverse_ms_per_query": ("_search_many", "navis.traverse"),
    "search_many.rerank_ms_per_query": ("_search_many", "navis.rerank"),
    "search_many.cache_replay_ms_per_query": ("_search_many",
                                              "navis.cache_replay"),
    "insert_many.seek_ms_per_insert": ("_insert_many", "navis.seek"),
    "insert_many.commit_ms_per_insert": ("_insert_many", "navis.commit"),
}
TRAVERSE = "navis.traverse"
FETCH = "navis.traverse/navis.fetch"


def planes(ops, modules, window=(0, 100)):
    """One host plane with the ``window`` annotation and one device plane;
    times in ms, ``ops`` as (name, start, duration, scope path)."""
    w0, w1 = window
    return [
        ("/host:CPU", [("python", [("window", w0 * MS, (w1 - w0) * MS)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [(n, s * MS, d * MS) for n, s, d in modules]),
            ("XLA Ops", [(n, s * MS, d * MS, p) for n, s, d, p in ops])])]


def test_scope_path_keeps_the_navis_names_in_order():
    assert scopes.scope_path(
        "jit(_insert_many)/jit(main)/vmap(navis.seek)/navis.traverse/while/"
        "body/navis.fetch/gather") == \
        "navis.seek/navis.traverse/navis.fetch"
    assert scopes.scope_path("jit(_search_many)/jit(main)/reduce_sum") == ""
    assert scopes.scope_path(
        "jit(_search_many)/vmap(navis.traverse)/broadcast_in_dim;"
        "jit(_search_many)/vmap(navis.traverse)/broadcast_in_dim") == \
        "navis.traverse"


def test_nested_ops_count_once():
    """A while's event covers its body's: the scope is their union, and
    each sub-scope its own ops' union."""
    got = scopes.reduce_planes(planes(
        [("%while.1", 10, 50, TRAVERSE),
         ("%fusion.2", 20, 10, FETCH),
         ("%fusion.3", 25, 10, FETCH),
         ("%fusion.4", 40, 10, "navis.traverse/navis.merge")],
        [("jit__search_many(1)", 10, 50)]))
    prog = got["_search_many"]
    assert prog[TRAVERSE] == pytest.approx(0.05)
    assert prog[FETCH] == pytest.approx(0.015)
    assert prog["navis.traverse/navis.merge"] == pytest.approx(0.01)
    assert prog[scopes.UNSCOPED] == 0


def test_time_outside_the_window_is_dropped():
    got = scopes.reduce_planes(planes(
        [("%while.1", 0, 30, TRAVERSE), ("%fusion.2", 90, 20, "")],
        [("jit__search_many(1)", 0, 30), ("jit__search_many(1)", 90, 20)],
        window=(10, 100)))
    assert got["_search_many"] == {TRAVERSE: pytest.approx(0.02),
                                   scopes.UNSCOPED: pytest.approx(0.01)}


def test_same_hlo_name_goes_to_its_own_program():
    got = scopes.reduce_planes(planes(
        [("%while.1", 0, 10, TRAVERSE), ("%while.1", 20, 30, "navis.commit"),
         ("%fusion.2", 60, 5, "navis.rerank")],
        [("jit__search_many(1)", 0, 10), ("jit__insert_many(2)", 20, 30),
         ("jit__search_many(1)", 60, 5)]))
    assert got == {
        "_search_many": {TRAVERSE: pytest.approx(0.01),
                         "navis.rerank": pytest.approx(0.005),
                         scopes.UNSCOPED: 0},
        "_insert_many": {"navis.commit": pytest.approx(0.03),
                         scopes.UNSCOPED: 0}}


def test_ops_with_no_scope_are_unscoped():
    """An unscoped op counts only where no scoped op runs, so the top-level
    scopes and ``(unscoped)`` add up to the program's busy time."""
    got = scopes.reduce_planes(planes(
        [("%fusion.1", 0, 10, ""), ("%while.2", 5, 20, "navis.cache_replay"),
         ("%copy.3", 30, 5, ""), ("%copy.4", 50, 5, "navis.other")],
        [("jit__search_many(1)", 0, 35), ("jit_unrelated(3)", 50, 5)]))
    assert got["_search_many"] == {
        "navis.cache_replay": pytest.approx(0.02),
        scopes.UNSCOPED: pytest.approx(0.01)}
    assert got["unrelated"] == {"navis.other": pytest.approx(0.005),
                                 scopes.UNSCOPED: 0}


def _msg(*fields):
    """A protobuf message of (field number, int | bytes | str | message)."""
    def varint(n):
        out = b""
        while True:
            out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_op_names_read_the_traced_hlo():
    """The ``/host:metadata`` plane's HLO of each program names every
    instruction's op, keyed by the program id; other planes add nothing."""
    def instruction(name, op_name):
        return _msg((1, name), (2, "while"), (7, _msg((1, "while"),
                                                      (2, op_name))))

    def program(pid, *instructions):
        hlo = _msg((1, _msg((1, "jit__f"), (3, _msg(
            (1, "main"), *[(2, i) for i in instructions])))))
        stat = _msg((1, 7), (6, hlo))
        return (4, _msg((1, pid), (2, _msg((1, pid), (5, stat)))))

    raw = _msg(
        (1, _msg((2, "/device:TPU:0"), program(5, instruction("x.1", "y")))),
        (1, _msg((2, "/host:metadata"),
                 program(11, instruction("while.3", "jit(f)/navis.commit/"
                                         "while"),
                         instruction("add.4", "jit(f)/add")),
                 program(2 ** 63 + 9, instruction("while.3", "jit(g)/w")))))
    assert scopes.op_names(raw) == {
        (11, "while.3"): "jit(f)/navis.commit/while",
        (11, "add.4"): "jit(f)/add",
        (2 ** 63 + 9, "while.3"): "jit(g)/w"}


def _rec(cell="tiny.mixed"):
    return {"cell": cell,
            "search_waves": [{"n": 64}, {"n": 64}],
            "insert_waves": [{"n": 16}] * 4}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_its_scope(name):
    program, scope = READERS[name]
    trace = {"scopes": {program: {scope: 0.32, scopes.UNSCOPED: 0.01}}}
    n = 128 if program == "_search_many" else 64
    got = run.load_reader("layer_metrics", name)(_rec(), trace)
    assert got == pytest.approx(320.0 / n)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_none_without_a_trace(name):
    assert run.load_reader("layer_metrics", name)(_rec(), None) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_none_without_its_scope(name):
    """A program traced before its stages were named has only
    ``(unscoped)``; its readers report nothing, never 0."""
    trace = {"scopes": {"_search_many": {scopes.UNSCOPED: 0.5},
                        "_insert_many": {scopes.UNSCOPED: 0.2}}}
    assert run.load_reader("layer_metrics", name)(_rec(), trace) is None
    program, scope = READERS[name]
    trace = {"scopes": {program: {scope: 0.0, scopes.UNSCOPED: 0.5}}}
    assert run.load_reader("layer_metrics", name)(_rec(), trace) is None


def test_scope_metrics_are_declared():
    declared = {m["name"]: m for m in BM["per_layer"]}
    for name, (program, _) in READERS.items():
        m = declared[name]
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m["moves"] == ("search_qps" if program == "_search_many"
                              else "insert_ips")


def test_run_trace_is_reduced_once(tmp_path, monkeypatch):
    """The first reader of a run reduces the trace beside it and keeps the
    split in the run's trace summary; the others reuse it."""
    trace_dir = tmp_path / "bench" / "out" / "trace" / "tiny.mixed"
    (trace_dir / "plugins").mkdir(parents=True)
    (trace_dir / "plugins" / "host.xplane.pb").write_bytes(b"")
    reader = tmp_path / "bench" / "layer_metrics" / "some_metric.py"
    read = []

    def fake_read(path):
        read.append(path)
        return planes([("%while.1", 0, 10, TRAVERSE)],
                      [("jit__search_many(1)", 0, 10)])

    monkeypatch.setattr(scopes, "read_planes", fake_read)
    summary = {"window_s": 0.1}
    for _ in range(2):
        got = scopes.ms_per(_rec(), summary, str(reader), "_search_many",
                            TRAVERSE)
        assert got == pytest.approx(10.0 / 128)
    assert read == [trace_dir / "plugins" / "host.xplane.pb"]
    assert summary["scopes"]["_search_many"][TRAVERSE] == pytest.approx(0.01)


# -- a trace recorded on the chip --------------------------------------------

def _chip_planes():
    return json.loads(gzip.decompress(
        (BENCH / "tests" / "data" / record_scopes_trace.NAME).read_bytes()))


@pytest.mark.parametrize("program", ["_search_many", "_insert_many"])
def test_chip_scopes_add_up_to_the_program(program):
    """A round of the tiny cell traced on one v5e
    (``record_scopes_trace.py``): in each program the top-level scopes and
    ``(unscoped)`` add up to its device time to within 1%."""
    planes_ = _chip_planes()
    split = scopes.reduce_planes(planes_)[program]
    whole = trace_reduce.reduce_planes(
        record_scopes_trace.without_scopes(planes_))["programs"][program]
    top = sum(s for path, s in split.items() if "/" not in path)
    assert top == pytest.approx(whole, rel=0.01)
    assert split[scopes.UNSCOPED] < 0.05 * whole
    stages = {p.split("/")[-1] for p in split} - {scopes.UNSCOPED}
    want = {"navis.entrance", "navis.traverse", "navis.fetch", "navis.score",
            "navis.merge", "navis.rerank", "navis.cache_replay"}
    if program == "_insert_many":
        want |= {"navis.encode", "navis.seek", "navis.select",
                 "navis.commit", "navis.link", "navis.entrance_update"}
    assert stages == want
