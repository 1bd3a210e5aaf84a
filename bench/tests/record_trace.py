"""Record the small chip trace that ``test_bench_harness.py`` reduces.

    python bench/tests/record_trace.py OUT_DIR

Runs one traced round of the tiny cell (``tiny.py``: two insert waves,
one search wave) on the chip and writes ``OUT_DIR/tiny_round.planes.json.gz``:
the planes of its ``*.xplane.pb`` as ``trace_reduce.read_planes`` gives
them, cut to what the reduction reads (the device's ``XLA Ops`` and
``XLA Modules`` lines, op names shortened, and the harness's host
annotations), which keeps the file small.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run, trace_reduce as T  # noqa: E402
from bench.tests.tiny import make_checkout  # noqa: E402


def extract(planes) -> list:
    keep = []
    for pname, lines in planes:
        if pname.startswith("/device:") and "TPU" in pname:
            keep.append((pname, [
                (ln, [(T.op_name(n) if ln == T.OPS_LINE else n, s, d)
                      for n, s, d in ev])
                for ln, ev in lines if ln in (T.OPS_LINE, T.MODULES_LINE)]))
        elif pname.startswith("/host:"):
            steps = [(ln, [e for e in ev
                           if e[0] in T.HOST_STEPS or e[0] == T.WINDOW])
                     for ln, ev in lines]
            keep.append((pname, [x for x in steps if x[1]]))
    return keep


def main(out_dir: str) -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return run.NO_DEVICE
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=out) as tmp:
        root = make_checkout(pathlib.Path(tmp))
        run.run("tiny.mixed", 1, 0.5, False, kind=dev.device_kind, root=root)
        run.run("tiny.mixed", 2, 1e-3, True, kind=dev.device_kind, root=root)
        planes = T.read_planes(T.find_trace(
            root / "bench" / "out" / "trace" / "tiny.mixed"))
    data = json.dumps(extract(planes), separators=(",", ":")).encode()
    (out / "tiny_round.planes.json.gz").write_bytes(gzip.compress(data, 9))
    print(json.dumps(T.reduce_planes(json.loads(data))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
