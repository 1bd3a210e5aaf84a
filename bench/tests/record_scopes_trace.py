"""Record the small chip trace that ``test_scopes.py`` reduces.

    python bench/tests/record_scopes_trace.py OUT_DIR

Runs one traced round of the tiny cell (``tiny.py``: two insert waves,
one search wave) on the chip, as ``record_trace.py`` does, and writes
``OUT_DIR/tiny_round_scopes.planes.json.gz``: the planes of its
``*.xplane.pb`` as ``scopes.read_planes`` gives them, cut to what
``trace_reduce`` and ``scopes`` read (the device's ``XLA Ops`` events,
each with its scope path, and ``XLA Modules`` events, and the harness's
host annotations).  Prints the run's metrics and both reductions.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run, scopes, trace_reduce as T  # noqa: E402
from bench.tests.tiny import make_checkout  # noqa: E402

NAME = "tiny_round_scopes.planes.json.gz"


def extract(planes) -> list:
    keep = []
    for pname, lines in planes:
        if pname.startswith("/device:") and "TPU" in pname:
            keep.append((pname, [
                (ln, [(T.op_name(ev[0]),) + tuple(ev[1:]) for ev in events])
                for ln, events in lines
                if ln in (T.OPS_LINE, T.MODULES_LINE)]))
        elif pname.startswith("/host:"):
            steps = [(ln, [e for e in ev
                           if e[0] in T.HOST_STEPS or e[0] == T.WINDOW])
                     for ln, ev in lines]
            keep.append((pname, [x for x in steps if x[1]]))
    return keep


def without_scopes(planes) -> list:
    """The planes as ``trace_reduce.reduce_planes`` reads them."""
    return [(p, [(ln, [tuple(ev[:3]) for ev in events])
                 for ln, events in lines]) for p, lines in planes]


def main(out_dir: str) -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_scopes_trace.py: no TPU", file=sys.stderr)
        return run.NO_DEVICE
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=out) as tmp:
        root = make_checkout(pathlib.Path(tmp))
        run.run("tiny.mixed", 1, 0.5, False, kind=dev.device_kind, root=root)
        res = run.run("tiny.mixed", 2, 1e-3, True, kind=dev.device_kind,
                      root=root)
        planes = extract(scopes.read_planes(T.find_trace(
            root / "bench" / "out" / "trace" / "tiny.mixed")))
    data = json.dumps(planes, separators=(",", ":")).encode()
    (out / NAME).write_bytes(gzip.compress(data, 9))
    planes = json.loads(data)
    print(json.dumps({"metrics": res["metrics"],
                      "trace": T.reduce_planes(without_scopes(planes)),
                      "scopes": scopes.reduce_planes(planes)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
