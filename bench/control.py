"""Read the control of a cell's compare on the chip.

    python bench/control.py --workload deep96.mixed --seconds 40 --seeds 1 2 3

For each seed, one window of the cell's own traffic at its own load (as a
run drives it), then the compare twice over the same waves: once of the
engine's answers (the sound reading) and once with the control in the
engine's place: the plain reference computed in bfloat16, one step below
the deployments' float32.  One JSON line per seed holds both sets of
numbers, and the read-back share of a fault: the read-back served by the
state the window started from, as if no insert had been committed.  The
control has to fail the cell's limits; the benchmark's own runs never
run it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import reference, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", str(run.OUT / "tpu_logs"))

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU; nothing run", file=sys.stderr)
        return run.NO_DEVICE
    run._set_compile_cache()
    from bench.corpus import corpus
    from bench.traffic import Traffic

    cell = run.find_cell(run.benchmark(), args.workload)
    cfg = run.load_json("configs", cell["config"])
    mix = run.load_json("mixes", cell["traffic"])
    k = cfg["engine"]["k"]
    base, cents = jax.block_until_ready(corpus(cfg))
    engine, restored, _ = run.open_index(cell["config"], cfg, base)
    for seed in args.seeds:
        traffic = Traffic(cfg, mix, seed, cents)
        run.warm(engine, restored, traffic)
        state, waves, elapsed = run.run_window(engine, restored, traffic,
                                               mix, args.seconds)
        count = int(state.store.count)
        run.fetch(waves)
        timed = list(waves)
        waves.append(run.read_back(engine, state, traffic, timed))
        del state
        # the insert commit left out: the read-back finds the state the
        # window started from
        unchanged = timed + [run.read_back(engine, restored, traffic, timed)]
        sound = run.compare(cfg, base, traffic, waves, count)
        ctrl = run.compare(cfg, base, traffic, waves, count,
                           answer=lambda q, c, live: reference.topk_bf16(
                               q, c, live, k=k))
        fault = run.compare(cfg, base, traffic, unchanged, count)
        print(json.dumps({
            "seed": seed, "window_s": elapsed,
            "program": sound, "program_correct":
                run.judge(sound, cfg["limits"])[0],
            "control": ctrl, "control_correct":
                run.judge(ctrl, cfg["limits"])[0],
            "insert_unchanged": fault["readback_miss"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
