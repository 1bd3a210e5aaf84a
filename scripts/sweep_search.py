"""Choose a deployment's search settings by a sweep on the chip.

    python scripts/sweep_search.py --config text768 \\
        --settings 2000:200:960 2400:200:960 --seeds 1 2 3 4 5 --limit 0.06

Opens the configuration's index as the benchmark does (``bench/run.py``'s
``open_index``: restored when a run of this checkout saved it, built and
saved otherwise).  Then for each setting ``e_search:s_search:max_hops``,
given cheapest first, it drives the cell's traffic
(``bench/mixes/mixed.json`` unless ``--mix``) on that index: per seed
``--rounds`` rounds of the closed loop, the read-back of every
acknowledged insert, and the harness's compare against the plain
reference.  A setting stops at its first seed whose ``search_miss`` or
``readback_miss`` is above ``--limit``; the first setting to hold every
seed at or under it is chosen, and the sweep ends there.

Writes one JSON line per (setting, seed) and a summary to stdout and to
``<out>/sweep.jsonl`` (``--out``, by default ``bench/out/sweep``).  Needs
a TPU; exits 3 without one.
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def miss(rec: dict) -> float:
    return max(rec["search_miss"], rec["readback_miss"])


def choose(settings, seeds, limit: float, one):
    """(the chosen setting or None, {setting: [records]}).

    ``one(setting, seed)`` runs a setting on a seed and returns its record.
    ``settings`` come cheapest first; each runs its seeds in order until
    one misses more than ``limit``, and the first to hold all of them is
    chosen without running the rest."""
    results = {}
    for setting in settings:
        for seed in seeds:
            rec = one(setting, seed)
            results.setdefault(setting, []).append(rec)
            if miss(rec) > limit:
                break
        else:
            return setting, results
    return None, results


def summary(results: dict) -> dict:
    return {s: {"seeds": len(r),
                "worst_search_miss": max(x["search_miss"] for x in r),
                "worst_readback_miss": max(x["readback_miss"] for x in r),
                "search_ms_per_query": min(x["search_ms_per_query"]
                                           for x in r)}
            for s, r in results.items()}


def peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def drive(engine, state, traffic, mix, rounds: int):
    """``rounds`` rounds of the closed loop from ``state``, one round per
    ``run_window`` call; returns (final state, waves, window seconds)."""
    from bench import run

    waves, elapsed = [], 0.0
    for r in range(rounds):
        state, more, s = run.run_window(engine, state, traffic, mix, 0.0,
                                        first=r)
        waves += more
        elapsed += s
    return state, waves, elapsed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", default="mixed")
    ap.add_argument("--settings", nargs="+", required=True,
                    help="e_search:s_search:max_hops, cheapest first")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--limit", type=float, default=0.06)
    ap.add_argument("--out", type=pathlib.Path,
                    default=ROOT / "bench" / "out" / "sweep")
    args = ap.parse_args(argv)

    from bench import run

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_search.py: no TPU; nothing run", file=sys.stderr)
        return run.NO_DEVICE
    run._set_compile_cache()
    from bench.corpus import corpus
    from bench.traffic import Traffic
    from repro.core import Engine

    args.out.mkdir(parents=True, exist_ok=True)
    sink = (args.out / "sweep.jsonl").open("a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    cfg = run.load_json("configs", args.config)
    mix = run.load_json("mixes", args.mix)
    base, cents = jax.block_until_ready(corpus(cfg))
    built_engine, built, build_s = run.open_index(args.config, cfg, base)
    emit({"config": args.config, "n_base": cfg["n_base"],
          "device": jax.devices()[0].device_kind, "build_s": build_s,
          "process_age_s": run.process_age_s(), "peak_bytes": peak_bytes()})

    engines = {}

    def one(setting: str, seed: int) -> dict:
        e, s, h = (int(x) for x in setting.split(":"))
        if setting not in engines:
            c = copy.deepcopy(cfg)
            c["engine"].update(e_search=e, s_search=s, max_hops=h)
            eng = Engine(run.engine_spec(c))
            eng.install_codec(built_engine.codec)
            engines[setting] = eng
        eng = engines[setting]
        traffic = Traffic(cfg, mix, seed, cents)
        t = time.perf_counter()
        run.warm(eng, built, traffic)
        warm_s = time.perf_counter() - t
        state, waves, elapsed = drive(eng, built, traffic, mix, args.rounds)
        count = int(state.store.count)
        hops_max = int(max(int(w["rounds"].max()) for w in waves
                           if w["kind"] == "search"))
        run.fetch(waves)
        search_s = [w["wall_s"] for w in waves if w["kind"] == "search"]
        insert_s = [w["wall_s"] for w in waves if w["kind"] == "insert"]
        waves.append(run.read_back(eng, state, traffic, waves))
        del state
        numbers = run.compare(cfg, base, traffic, waves, count)
        rec = {"setting": setting, "seed": seed, "warm_s": warm_s,
               "window_s": elapsed,
               "search_ms_per_query": 1e3 * sum(search_s) / (
                   len(search_s) * mix["search_wave"]),
               "insert_wave_ms": 1e3 * sum(insert_s) / len(insert_s),
               "max_serial_rounds": hops_max,
               "correct": run.judge(numbers, cfg["limits"])[0],
               "peak_bytes": peak_bytes(), **numbers}
        emit(rec)
        return rec

    chosen, results = choose(args.settings, args.seeds, args.limit, one)
    emit({"summary": summary(results), "chosen": chosen,
          "limit": args.limit, "process_age_s": run.process_age_s()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
