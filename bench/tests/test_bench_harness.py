"""CPU tests of the chip benchmark's harness, at a tiny deployment.

The tiny cell (``tiny.py``) runs through the harness's own phases: the
index is built, saved and restored, a window of one round drives
``Engine.insert_many`` and ``Engine.search_many``, and the answers are
compared with the plain reference.  The timed path is then broken
underneath in each way the benchmark's cells can break, and the control
(the reference in bfloat16) takes the engine's place: each must come out
not correct.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import index_cache, reference, run, trace_reduce  # noqa: E402
from bench.tests.tiny import CONFIG, MIX, make_checkout  # noqa: E402
from bench.corpus import corpus  # noqa: E402
from bench.traffic import CHUNK_ROUNDS, Traffic, make_chunk  # noqa: E402
from repro.core import Engine  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
KIND = "TPU v5 lite"          # whose peaks the per-layer readers see
DATA = BENCH / "tests" / "data"


def _run(root, seed, seconds=1e-3):
    return run.run("tiny.mixed", seed, seconds, False, kind=KIND, root=root)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A tiny checkout whose index the first run built and saved."""
    root = make_checkout(tmp_path_factory.mktemp("checkout"))
    first = _run(root, 11)
    return root, first


# -- names --------------------------------------------------------------------

@pytest.mark.parametrize("kind,name", sorted(
    {("configs", w["config"]) for w in BM["workloads"]} |
    {("mixes", w["traffic"]) for w in BM["workloads"]}))
def test_config_and_mix_found_by_name(kind, name):
    got = run.load_json(kind, name)
    if kind == "configs":
        spec = run.engine_spec(got)
        assert spec.dim == got["dim"]
        assert spec.n_max == got["n_base"] + got["headroom"]
        assert set(got["limits"]) <= {"search_miss", "readback_miss",
                                      "dist_rel_err", "bad_ids",
                                      "count_gap", "dropped"}
    else:
        assert got["loop"] == "closed" and got["clients"] == 1


@pytest.mark.parametrize("section,name", [
    (s, m["name"]) for s in ("end_to_end", "per_layer") for m in BM[s]])
def test_metric_reader_found_by_name(section, name):
    kind = "end_to_end" if section == "end_to_end" else "layer_metrics"
    assert callable(run.load_reader(kind, name))


@pytest.mark.parametrize("what", ["cell", "configs", "mixes", "reader"])
def test_unknown_name_is_an_error(what):
    with pytest.raises(run.UnknownName):
        if what == "cell":
            run.find_cell(BM, "no.such.cell")
        elif what == "reader":
            run.load_reader("layer_metrics", "no_such_metric")
        else:
            run.load_json(what, "no_such_name")


# -- the reference ------------------------------------------------------------

def test_reference_matches_numpy_topk():
    rng = np.random.default_rng(0)
    corpus_np = rng.normal(size=(3000, 24)).astype(np.float32) * 3
    q = rng.normal(size=(20, 24)).astype(np.float32) * 3
    live = 2500
    ids, d = reference.topk(jnp.asarray(q), jnp.asarray(corpus_np), live,
                            k=10)
    exact = ((q[:, None, :] - corpus_np[None, :live]) ** 2).sum(-1)
    want = np.argsort(exact, axis=1)[:, :10]
    np.testing.assert_array_equal(np.asarray(ids), want)
    np.testing.assert_allclose(np.asarray(d),
                               np.take_along_axis(exact, want, 1),
                               rtol=1e-6)
    # the control ranks and measures in bfloat16
    _, d16 = reference.topk_bf16(jnp.asarray(q), jnp.asarray(corpus_np),
                                 live, k=10)
    assert np.abs(np.asarray(d16) - np.take_along_axis(
        exact, want, 1)).max() > 1e-3


# -- traffic ------------------------------------------------------------------

def test_traffic_is_a_function_of_seed_and_round():
    """A round's traffic does not depend on which chunk made it, and
    seeds past 32 bits give their own traffic."""
    _, cents = corpus(CONFIG)
    seed = 2 ** 40 + 5
    t = Traffic(CONFIG, MIX, seed, cents)
    r = CHUNK_ROUNDS + 3
    alone = make_chunk(t.key, t.order_key, jnp.int32(r), cents, t.query_set,
                       insert_waves=MIX["insert_waves"],
                       insert_wave=MIX["insert_wave"],
                       search_waves=MIX["search_waves"],
                       search_wave=MIX["search_wave"],
                       noise=CONFIG["noise"], drift=MIX["insert_drift"])
    for got, want in zip(t.round(r), alone):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want[0]))
    low = Traffic(CONFIG, MIX, seed & 0xFFFFFFFF, cents)
    for got, other in zip(t.round(0), low.round(0)):
        assert not np.array_equal(np.asarray(got), np.asarray(other))


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 7])
def test_every_pass_searches_the_query_set_once(seed):
    """Each pass through the query set takes every query once, in an
    order of its own, and every seed searches the same set."""
    _, cents = corpus(CONFIG)
    t = Traffic(CONFIG, MIX, seed, cents)
    per_pass = MIX["query_set"] // (MIX["search_waves"] * MIX["search_wave"])
    want = np.sort(np.asarray(t.query_set), axis=0)
    passes = []
    for p in range(3):
        qs = np.concatenate([np.asarray(t.round(p * per_pass + j)[1])
                             .reshape(-1, CONFIG["dim"])
                             for j in range(per_pass)])
        np.testing.assert_array_equal(np.sort(qs, axis=0), want)
        passes.append(qs)
    assert not np.array_equal(passes[0], passes[1])
    other = Traffic(CONFIG, MIX, seed + 1, cents)
    np.testing.assert_array_equal(np.asarray(other.query_set),
                                  np.asarray(t.query_set))


# -- index persistence --------------------------------------------------------

def test_index_restore_equals_build(checkout):
    root, first = checkout
    cfg = CONFIG
    base, cents = corpus(cfg)
    cache = root / "bench" / ".index_cache"
    eng, restored, build_s = run.open_index("tiny", cfg, base, cache=cache,
                                            root=root)
    assert build_s is None
    fresh_eng = Engine(run.engine_spec(cfg))
    b = cfg["build"]
    built = fresh_eng.build(jax.random.PRNGKey(cfg["data_seed"]), base,
                            build_block=b["build_block"],
                            build_e_pos=b["build_e_pos"], alpha=b["alpha"])
    for x, y in zip(jax.tree.leaves(built), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    q = Traffic(cfg, run.load_json("mixes", "tiny", root / "bench"), 5,
                cents).round(0)[1][0]
    np.testing.assert_array_equal(
        np.asarray(fresh_eng.search_many(built, q)[0]),
        np.asarray(eng.search_many(restored, q)[0]))

    # a change to the program's source misses the saved index
    src = root / "src" / "repro" / "core" / "__init__.py"
    text = src.read_text()
    try:
        src.write_text(text + "\n# changed\n")
        assert index_cache.restore("tiny", Engine(run.engine_spec(cfg)),
                                   base, cache=cache, root=root) is None
    finally:
        src.write_text(text)
    assert index_cache.restore("tiny", Engine(run.engine_spec(cfg)), base,
                               cache=cache, root=root) is not None


# -- a run, sound and broken --------------------------------------------------

def test_window_end_to_end_is_correct(checkout):
    root, first = checkout
    res = _run(root, 12)
    for r in (first, res):
        assert r["correct"] is True, r["compared"]
        assert list(r)[-1] == "compared"
        assert set(r["metrics"]) == {m["name"] for m in BM["end_to_end"]}
        assert r["attempted"] == 16 + 16 and r["failed"] == 0
    saved = json.loads((root / "bench" / "out" /
                        "tiny.mixed-12.json").read_text())
    assert saved["numbers"]["readback_miss"] == 0
    assert saved["record"]["build_s"] is None


def _insert_state_unchanged(orig):
    def broken(self, state, vectors, valid=None):
        stats, _ = orig(self, state, vectors, valid)
        return stats, state
    return "_insert_many", broken


def _half_batch_left_out(orig):
    def broken(self, state, queries):
        h = queries.shape[0] // 2
        ids, dists, stats, state = orig(self, state, queries[:h])

        def pad(x, v):
            return jnp.concatenate([x, jnp.full(x.shape, v, x.dtype)])
        stats = jax.tree.map(lambda x: jnp.concatenate([x, x]), stats)
        return pad(ids, -1), pad(dists, reference.BIG), stats, state
    return "_search_many", broken


def _answer_altered(orig):
    def broken(self, state, queries):
        ids, dists, stats, state = orig(self, state, queries)
        return ids.at[:, 0].add(1), dists, stats, state
    return "_search_many", broken


FAULTS = {"insert_state_unchanged": (_insert_state_unchanged,
                                     "_insert_many"),
          "half_batch_left_out": (_half_batch_left_out, "_search_many"),
          "answer_altered": (_answer_altered, "_search_many")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, fault):
    root, _ = checkout
    make, attr = FAULTS[fault]
    name, broken = make(getattr(Engine, attr))
    monkeypatch.setattr(Engine, name, broken)
    res = _run(root, 13)
    assert res["correct"] is False, res["compared"]


def test_control_is_not_correct(checkout):
    """The reference in bfloat16, in the engine's place, fails."""
    root, _ = checkout
    cfg = CONFIG
    mix = run.load_json("mixes", "tiny", root / "bench")
    base, cents = corpus(cfg)
    eng, state, _ = run.open_index("tiny", cfg, base,
                                   cache=root / "bench" / ".index_cache",
                                   root=root)
    traffic = Traffic(cfg, mix, 14, cents)
    state, waves, _ = run.run_window(eng, state, traffic, mix, 1e-3)
    count = int(state.store.count)
    run.fetch(waves)
    waves.append(run.read_back(eng, state, traffic, waves))
    sound = run.compare(cfg, base, traffic, waves, count)
    assert run.judge(sound, cfg["limits"])[0], sound
    ctrl = run.compare(cfg, base, traffic, waves, count,
                       answer=lambda q, c, live: reference.topk_bf16(
                           q, c, live, k=cfg["engine"]["k"]))
    assert not run.judge(ctrl, cfg["limits"])[0], ctrl
    assert ctrl["dist_rel_err"] > cfg["limits"]["dist_rel_err"]


def test_no_tpu_means_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BM["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


# -- trace reduction ----------------------------------------------------------

def test_trace_reduce_on_built_planes():
    """Overlapping ops count once as busy; gaps go to the host step that
    overlaps them; nothing outside the window counts."""
    ms = 1_000_000
    planes = [
        ("/host:CPU", [("python", [
            ("window", 10 * ms, 100 * ms),
            ("traffic", 10 * ms, 20 * ms),
            ("dispatch:search_many", 30 * ms, 1 * ms),
            ("wait:search_many", 31 * ms, 49 * ms),
            ("record", 80 * ms, 10 * ms)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit__search_many(42)", 30 * ms, 50 * ms),
                             ("jit_other(7)", 0, 5 * ms)]),
            ("XLA Ops", [("%while.1 = s32[] while(...)", 30 * ms, 50 * ms),
                         ("%fusion.2 = f32[8] fusion(...)", 40 * ms, 5 * ms),
                         ("%fusion.3 = f32[8] fusion(...)", 0, 5 * ms)])]),
    ]
    got = trace_reduce.reduce_planes(planes)
    assert got["window_s"] == pytest.approx(0.1)
    assert got["busy_s"] == pytest.approx(0.05)
    assert got["programs"] == {"_search_many": pytest.approx(0.05)}
    assert got["device_ops"][0] == ["%while.1", pytest.approx(0.05)]
    gaps = dict(got["idle_gaps"])
    assert gaps == {"traffic": pytest.approx(0.02),
                    "record": pytest.approx(0.01),
                    "other": pytest.approx(0.02)}


def test_trace_reduce_on_chip_trace():
    """A round of the tiny cell traced on one v5e (``record_trace.py``).
    Read off its planes by hand: the ``window`` annotation lasts
    48.182564 ms; ``jit__insert_many`` ran twice, 6.794473 + 6.547633 ms,
    and ``jit__search_many`` once, 7.462634 ms."""
    import gzip

    planes = json.loads(gzip.decompress(
        (DATA / "tiny_round.planes.json.gz").read_bytes()))
    got = trace_reduce.reduce_planes(planes)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(0.048182564)
    assert got["programs"]["_insert_many"] == pytest.approx(0.013342106)
    assert got["programs"]["_search_many"] == pytest.approx(0.007462634)
    # busy and idle split the window; the longest gaps wait on the host
    idle = sum(s for _, s in got["idle_gaps"])
    assert got["busy_s"] + idle == pytest.approx(got["window_s"])
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["idle_gaps"][0][0] == "dispatch:insert_many"
    assert len(got["device_ops"]) == 10
