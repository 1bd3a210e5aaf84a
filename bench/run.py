"""Run one cell of the chip benchmark once.

    python bench/run.py --workload deep96.mixed --seed 7 --seconds 30 --trace 0

A cell (``BENCHMARK.json``'s ``workloads``) names a deployment
(``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``); the deployment names the driver that
runs its engine (``bench/drivers/<driver>.py``, ``single`` when it names
none; the contract is in ``drivers/__init__.py``).  A run:

1. loads them by name, and the readers of the cell's metrics;
2. has the driver restore the deployment's index on the cell's chips, or
   build and save it on the first run in this checkout
   (``index_cache.py``);
3. makes the run's traffic on the device from ``--seed`` (``traffic.py``);
4. warms every program and state signature the window uses, and discards
   what the warm-up returns;
5. drives a closed loop of rounds (insert waves through the driver's
   ``insert``, then search waves through its ``search``, one state
   threaded through) until ``--seconds`` have passed, timing every wave
   from dispatch to ``block_until_ready``;
6. after the window, looks every acknowledged insert up by a copy of
   itself on the window's final state (``read_back``), reads the
   device's peak memory,
   frees the engine's state and compares every search answer with the
   plain reference (``reference.py``) over the corpus live at its wave;
7. prints the metrics as one JSON object, the last line of stdout.

With ``--trace 1`` the window runs under the profiler and the line holds
the cell's per-layer metrics in place of its end-to-end ones.  Earlier
lines of stdout are ``name=value`` logs; the numbers compared, each with
its limit, are the last lines of stderr.  Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 3.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
COMPILE_CACHE = BENCH / ".jax_cache"
NO_DEVICE = 3
# the part of a --trace 1 window that the profiler records: over longer
# spans the device trace of one v5e drops events
TRACE_S = 10.0


class UnknownName(LookupError):
    """A cell, configuration, mix, driver or metric that has no file."""


def log(name: str, value) -> None:
    print(f"{name}={value}", flush=True)


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's clock."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - start


# -- finding things by name ---------------------------------------------------

def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise UnknownName(f"no workload named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str, bench: pathlib.Path = BENCH) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a mix."""
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise UnknownName(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def _load_module(kind: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(kind: str, name: str, bench: pathlib.Path = BENCH):
    """``read(rec, trace)`` of ``bench/<kind>/<name>.py``."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise UnknownName(f"no reader for metric {name!r} ({path})")
    return _load_module(kind, path).read


def load_driver(cfg: dict, bench: pathlib.Path = BENCH):
    """The module of the driver that configuration ``cfg`` names."""
    from bench import drivers

    path = drivers.path(bench, cfg)
    if not path.is_file():
        raise UnknownName(f"no driver named {path.stem!r} ({path})")
    return _load_module("drivers", path)


def cell_metrics(bm: dict, section: str, cell: str) -> list[dict]:
    """The metrics of ``section`` that cell ``cell`` reports."""
    return [m for m in bm[section]
            if cell in m.get("workloads", [cell])]


# -- set-up, window -----------------------------------------------------------

def warm(drv, handle, state, traffic) -> None:
    """Run each program on each state signature the window gives it
    (restored, after an insert wave, after a search wave); discard all."""
    import jax

    ins, qs = traffic.round(0)
    v, q = ins[0], qs[0]
    s = state
    for op in ("insert", "insert", "search", "insert", "search"):
        if op == "insert":
            out = drv.insert(handle, s, v)
            s = out[1]
        else:
            out = drv.search(handle, s, q)
            s = out[3]
        jax.block_until_ready(out)
    jax.block_until_ready(drv.search(handle, state, q))


def _annotate(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def run_window(drv, handle, state, traffic, mix: dict, seconds: float, *,
               tracing: bool = False, first: int = 0):
    """The closed loop: rounds ``first``, ``first + 1``, … until
    ``seconds`` have passed, at least one.  Returns (state, waves,
    elapsed seconds): each
    wave is a dict with its kind, round, wall seconds and outputs (left
    on the device until the window has closed)."""
    import jax

    note = _annotate(tracing)
    waves = []
    t0 = time.perf_counter()
    r = first
    with note("window"):
        while r == first or time.perf_counter() - t0 < seconds:
            with note("traffic"):
                ins, qs = traffic.round(r)
            for w in range(mix["insert_waves"]):
                with note("traffic"):
                    v = ins[w]
                t = time.perf_counter()
                with note("dispatch:insert_many"):
                    dropped, state = drv.insert(handle, state, v)
                with note("wait:insert_many"):
                    jax.block_until_ready((dropped, state))
                wall = time.perf_counter() - t
                with note("record"):
                    waves.append(dict(kind="insert", round=r, wave=w,
                                      wall_s=wall, n=v.shape[0],
                                      dropped=dropped))
            for w in range(mix["search_waves"]):
                with note("traffic"):
                    q = qs[w]
                t = time.perf_counter()
                with note("dispatch:search_many"):
                    ids, dists, rounds, state = drv.search(handle, state, q)
                with note("wait:search_many"):
                    jax.block_until_ready((ids, dists, rounds, state))
                wall = time.perf_counter() - t
                with note("record"):
                    waves.append(dict(kind="search", round=r, wave=w,
                                      wall_s=wall, n=q.shape[0],
                                      ids=ids, dists=dists,
                                      rounds=rounds))
            r += 1
    return state, waves, time.perf_counter() - t0


def fetch(waves: list[dict]) -> None:
    """Bring every wave's outputs to the host, in place."""
    import numpy as np

    for w in waves:
        for k in ("dropped", "ids", "dists", "rounds"):
            if k in w:
                w[k] = np.asarray(w[k])


def acknowledged(traffic, waves: list[dict]):
    """The inserts the window acknowledged, in the order of their
    acknowledgement, which is the order in which every driver numbers new
    vectors (``n_base``, ``n_base + 1``, …).  Needs fetched waves."""
    import jax.numpy as jnp
    import numpy as np

    parts = []
    for w in waves:
        if w["kind"] == "insert":
            keep = ~np.asarray(w["dropped"], bool)
            parts.append(traffic.round(w["round"])[0][w["wave"]]
                         [jnp.asarray(keep)])
    dim = traffic.cents.shape[1]
    return jnp.concatenate(parts) if parts else jnp.zeros((0, dim))


def read_back(drv, handle, state, traffic, waves: list[dict]) -> dict:
    """After the window, every acknowledged insert looked up by a copy of
    itself, in search waves of the window's size through the driver's
    ``search`` on ``state`` (the window's final state): each is
    its own nearest neighbour.  Returns the lookups as a wave of kind
    ``readback`` (``picks``: which acknowledged inserts, in the order of
    their acknowledgement); the states the waves return are discarded.
    Needs fetched waves."""
    import jax.numpy as jnp
    import numpy as np

    inserted = acknowledged(traffic, waves)
    n = inserted.shape[0]
    size = traffic.mix["search_wave"]
    picks = np.arange(n)
    ids, dists = [], []
    for i in range(0, n, size):
        # a short last wave is filled with the first inserts again
        rows = jnp.asarray(np.concatenate(
            [picks[i:i + size], np.resize(picks, size)])[:size])
        out = drv.search(handle, state, inserted[rows])
        m = min(size, n - i)
        ids.append(np.asarray(out[0])[:m])
        dists.append(np.asarray(out[1])[:m])
    k = drv.k(handle)
    return dict(kind="readback", picks=picks,
                ids=np.concatenate(ids) if ids else np.zeros((0, k), int),
                dists=np.concatenate(dists) if dists
                else np.zeros((0, k), np.float32))


# -- correctness ----------------------------------------------------------------

def compare(cfg: dict, base, traffic, waves: list[dict], final_count: int,
            answer=None) -> dict:
    """Every number compared, as ``{name: value}``.

    The reference corpus is the base followed by every acknowledged
    insert (``acknowledged``).  Search wave ``j`` is judged against the
    corpus live when it was dispatched; the read-back wave against the
    corpus at the window's close: ``readback_miss`` is the share of the
    inserts it looks up that it does not return.  ``answer(queries, corpus, live)``, when given, stands in
    for the engine's (ids, distances): it is how the control is read.
    """
    import jax.numpy as jnp
    import numpy as np

    from bench import reference

    k = cfg["engine"]["k"]
    n_base = cfg["n_base"]
    n_max = n_base + cfg["headroom"]
    acked_after, acked, dropped = [], 0, 0
    for w in waves:
        if w["kind"] == "insert":
            n_drop = int(np.asarray(w["dropped"], bool).sum())
            dropped += n_drop
            acked += w["n"] - n_drop
            acked_after.append(n_base + acked)
    inserted = acknowledged(traffic, waves)
    corpus = jnp.concatenate([base, inserted.astype(base.dtype)])
    corpus = jnp.pad(corpus, ((0, n_max - corpus.shape[0]), (0, 0)))

    found = total = back_missed = back_total = bad = 0
    err = 0.0
    n_prior = 0             # insert waves before this one
    for w in waves:
        if w["kind"] == "insert":
            n_prior += 1
            continue
        if w["kind"] == "readback":
            live = n_base + acked
            q = inserted[jnp.asarray(w["picks"])]
        else:
            live = acked_after[n_prior - 1] if n_prior else n_base
            q = traffic.round(w["round"])[1][w["wave"]]
        if answer is None:
            ids, dists = np.asarray(w["ids"]), np.asarray(w["dists"])
        else:
            ids, dists = (np.asarray(x) for x in answer(q, corpus, live))
        ok = (ids >= 0) & (ids < live)
        dup = np.array([len(set(r[m])) < m.sum() for r, m in zip(ids, ok)])
        bad += int((~ok).sum() + dup.sum())
        exact = np.asarray(reference.dist_of(q, corpus,
                                             np.where(ok, ids, -1)))
        rel = np.abs(dists - exact) / np.maximum(exact, 1e-30)
        if ok.any():
            err = max(err, float(rel[ok].max()))
        if w["kind"] == "readback":
            want = n_base + np.asarray(w["picks"])
            back_missed += int((~(ids == want[:, None]).any(-1)).sum())
            back_total += len(want)
            continue
        t_ids = np.asarray(reference.topk(q, corpus, live, k=k)[0])
        hit = (t_ids[:, :, None] == ids[:, None, :]).any(-1) & (t_ids >= 0)
        found += int(hit.sum())
        total += int((t_ids >= 0).sum())
    return {
        "search_miss": 1.0 - found / max(total, 1),
        "readback_miss": back_missed / max(back_total, 1),
        "dist_rel_err": err,
        "bad_ids": bad,
        "count_gap": abs(final_count - (n_base + acked)),
        "dropped": dropped,
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}) over the limited numbers."""
    shown = {name: {"value": numbers[name], "limit": lim}
             for name, lim in limits.items()}
    return all(numbers[n] <= lim for n, lim in limits.items()), shown


# -- the run ------------------------------------------------------------------

def _set_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept; ``repro.compile_cache`` honours the variable."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def widths(cfg: dict) -> dict:
    e = cfg["engine"]
    return {"dim": cfg["dim"], "r": e["r"], "pq_m": e["pq_m"],
            "beam_width": e["beam_width"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    chips = find_cell(benchmark(), args.workload)["chips"]
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s). Nothing run.",
              file=sys.stderr)
        return NO_DEVICE
    _set_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 kind=devs[0].device_kind)
    print(json.dumps(result), flush=True)
    for name, s in result["compared"].items():
        print(f"compared {name}={s['value']!r} limit={s['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


def run(workload: str, seed: int, seconds: float, tracing: bool, *,
        kind: str, root: pathlib.Path = ROOT) -> dict:
    """One run of cell ``workload`` from the checkout at ``root``; returns
    the result line's object.  ``kind`` is the device kind whose peaks
    the metrics read."""
    import jax

    from bench import trace_reduce
    from bench.corpus import corpus
    from bench.traffic import Traffic

    bench, out = root / "bench", root / "bench" / "out"
    bm = benchmark(root)
    cell = find_cell(bm, workload)
    cfg = load_json("configs", cell["config"], bench)
    mix = load_json("mixes", cell["traffic"], bench)
    section = "per_layer" if tracing else "end_to_end"
    metrics = cell_metrics(bm, section, cell["name"])
    readers = {m["name"]: load_reader(
        "layer_metrics" if tracing else "end_to_end", m["name"], bench)
        for m in metrics}
    peaks = json.loads((bench / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise UnknownName(f"no peaks for device kind {kind!r}")
    devs = jax.devices()
    log("device", f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}")

    drv = load_driver(cfg, bench)
    base, cents = jax.block_until_ready(corpus(cfg))
    handle, state, build_s = drv.open(
        cell["config"], cfg, base, chips=cell["chips"],
        cache=bench / ".index_cache", root=root)
    traffic = Traffic(cfg, mix, seed, cents)
    jax.block_until_ready(traffic.chunk(0))
    warm(drv, handle, state, traffic)
    ctr0 = drv.counters(handle, state)
    compiled = drv.compiled(handle)
    trace_dir = out / "trace" / cell["name"]
    if tracing:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = process_age_s()
    log("setup_s", setup_s)

    # a traced run records the first TRACE_S of its window; the rest runs
    # untraced, and its answers are compared as well
    state, waves, elapsed = run_window(
        drv, handle, state, traffic, mix,
        min(seconds, TRACE_S) if tracing else seconds, tracing=tracing)
    ctr = drv.counters(handle, state)
    measured = len(waves)
    if tracing:
        jax.profiler.stop_trace()
        log("traced_s", elapsed)
        if elapsed < seconds:
            state, rest, more = run_window(
                drv, handle, state, traffic, mix, seconds - elapsed,
                first=waves[-1]["round"] + 1)
            waves += rest
            elapsed += more
    log("window_s", elapsed)
    log("compiles_in_window", drv.compiled(handle) - compiled)
    final_count = drv.count(handle, state)
    # the counters are int32 on the device and may wrap; a window's
    # increments stay far below 2**32
    ctr = {side: {k: (v - ctr0[side][k]) % 2 ** 32
                  for k, v in ctr[side].items()} for side in ctr}
    fetch(waves)
    waves.append(read_back(drv, handle, state, traffic, waves))
    device = device_info()
    del state
    summary = None
    if tracing:
        t = time.perf_counter()
        summary = trace_reduce.reduce_trace(
            trace_reduce.find_trace(trace_dir))
        log("trace_reduce_s", time.perf_counter() - t)
        device = {**device, "busy_s": summary["busy_s"],
                  "window_s": summary["window_s"]}

    t = time.perf_counter()
    numbers = compare(cfg, base, traffic, waves, final_count)
    log("compare_s", time.perf_counter() - t)
    correct, shown = judge(numbers, cfg["limits"])
    log("recall_at_10", 1.0 - numbers["search_miss"])

    # the metrics read the waves that were measured: in a traced run,
    # those of its traced part
    rec = {"cell": cell["name"], "seed": seed, "seconds": seconds,
           "window_s": elapsed, "setup_s": setup_s, "build_s": build_s,
           "widths": widths(cfg), "peaks": peaks[kind], "counters": ctr,
           "search_waves": [{"wall_s": w["wall_s"], "n": w["n"],
                             "rounds": w["rounds"].tolist()}
                            for w in waves[:measured]
                            if w["kind"] == "search"],
           "insert_waves": [{"wall_s": w["wall_s"], "n": w["n"],
                             "dropped": int(w["dropped"].sum())}
                            for w in waves[:measured]
                            if w["kind"] == "insert"]}
    values = {}
    for m in metrics:
        v = readers[m["name"]](rec, summary)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct,
              "attempted": sum(w["n"] for w in waves
                               if w["kind"] != "readback"),
              "failed": numbers["dropped"], "metrics": values,
              "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["compared"] = shown

    out.mkdir(parents=True, exist_ok=True)
    suffix = "-trace" if tracing else ""
    (out / f"{cell['name']}-{seed}{suffix}.json").write_text(
        json.dumps({**result, "numbers": numbers, "record": rec,
                    "trace": summary}))
    for side in ("search", "insert"):
        log(f"io_counters[{side}]", ctr[side])
    return result


if __name__ == "__main__":
    sys.exit(main())
