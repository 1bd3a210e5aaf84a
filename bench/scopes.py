"""Device time of the engine's named stages, from a profiler trace.

The engine wraps each NAVIS stage in a ``jax.named_scope`` whose name
starts ``navis.`` (``navis.entrance``, ``navis.traverse`` and its
``navis.fetch`` / ``navis.score`` / ``navis.merge`` hop, ``navis.rerank``,
``navis.cache_replay``, ``navis.seek``, ``navis.select``, ``navis.encode``,
``navis.commit`` with ``navis.link`` and ``navis.entrance_update``).  JAX
writes the scope stack into each HLO instruction's ``op_name`` metadata.
The device trace names each op event of a device's ``XLA Ops`` line by
its instruction (``%while.628 = ...``), and keeps each traced program's
optimized HLO, with that metadata, in its ``/host:metadata`` plane.  The
trace's per-event stats leave the op name out for ``while`` and
``conditional`` ops, whose events span their bodies, and ``ProfileData``
does not expose the HLO; so ``op_names`` reads it from the trace file's
protobuf itself.  So each op event names its stage: its scope path is the
``navis.*`` names of its op name, outermost first
(``navis.seek/navis.traverse/navis.fetch``).

``reduce_planes`` gives ``{program: {scope path: seconds}}``: each op event
goes to the jitted program (``XLA Modules`` event) that encloses it, since
HLO names such as ``%while.628`` repeat across programs.  A path's seconds
are the union of the intervals of its ops and of the ops of the paths
below it, clipped to the harness's ``window``: an op event of a ``while``
already covers the events of its body, so a sum would count them twice.
``(unscoped)`` is the time ops with no ``navis.*`` name run and no scoped
op does.  So in each program the top-level paths plus ``(unscoped)`` are
its device busy time.

The per-layer readers call ``ms_per``.  The first call of a traced run
reduces the run's trace and keeps the result in the run's trace summary
under ``scopes``, which the harness then writes into the run's record
(``bench/out/<cell>-<seed>-trace.json``); the other readers reuse it.  A
program traced with no ``navis.*`` scope (a program before the scopes were
named) has only ``(unscoped)``, and its readers report nothing.
"""
from __future__ import annotations

import bisect
import pathlib
import re
from collections import defaultdict

from bench import trace_reduce as T

METADATA_PLANE = "/host:metadata"
SCOPE = re.compile(r"navis\.\w+")
UNSCOPED = "(unscoped)"
# the waves of the run record that each program served
WAVES = {"_search_many": "search_waves", "_insert_many": "insert_waves"}


def scope_path(op_name: str) -> str:
    """``jit(_insert_many)/vmap(navis.seek)/navis.traverse/while/body/
    navis.fetch/gather`` -> ``navis.seek/navis.traverse/navis.fetch``;
    ``""`` for an op outside every ``navis.*`` scope.  An instruction XLA
    merged from several carries their names joined by ``;``: the first
    counts."""
    return "/".join(SCOPE.findall(op_name.split(";")[0]))


def _length(intervals) -> float:
    return sum(e - s for s, e in T._union(intervals))


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, value) of each field of the protobuf message in
    ``buf[start:end]``: an int for a varint, a (start, end) range for a
    length-delimited field; fixed-width fields are skipped."""
    end = len(buf) if end is None else end

    def varint(i):
        shift = value = 0
        while True:
            b = buf[i]
            value |= (b & 0x7F) << shift
            shift, i = shift + 7, i + 1
            if b < 0x80:
                return value, i

    i = start
    while i < end:
        key, i = varint(i)
        wire = key & 7
        if wire == 0:
            value, i = varint(i)
            yield key >> 3, value
        elif wire == 2:
            n, i = varint(i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def op_names(raw: bytes) -> dict:
    """``{(program id, instruction name): op name}`` from a serialized
    ``XSpace``: its ``/host:metadata`` plane keeps each traced program's
    optimized ``HloProto`` as a bytes stat of the event metadata whose key
    is the program id.  Field numbers are those of tsl's ``xplane.proto``
    (``XSpace.planes`` 1; ``XPlane.name`` 2, ``event_metadata`` map 4;
    ``XEventMetadata.stats`` 5; ``XStat.bytes_value`` 6) and of xla's
    ``hlo.proto`` (``HloProto.hlo_module`` 1; ``HloModuleProto.computations``
    3; ``HloComputationProto.instructions`` 2; ``HloInstructionProto.name``
    1, ``metadata`` 7; ``OpMetadata.op_name`` 2)."""
    def text(r):
        return raw[r[0]:r[1]].decode("utf-8", "replace")

    def walk(r, *numbers):
        """The messages reached from the one at ``r`` down the path of
        field ``numbers``."""
        if not numbers:
            yield r
            return
        for f, v in _fields(raw, *r):
            if f == numbers[0] and isinstance(v, tuple):
                yield from walk(v, *numbers[1:])

    out = {}
    for plane in walk((0, len(raw)), 1):
        if [text(r) for r in walk(plane, 2)] != [METADATA_PLANE]:
            continue
        for entry in walk(plane, 4):
            program = dict(_fields(raw, *entry)).get(1)
            # metadata -> stats -> HloProto -> module -> computations ->
            # instructions
            for ins in walk(entry, 2, 5, 6, 1, 3, 2):
                ins = dict(_fields(raw, *ins))
                meta = dict(_fields(raw, *ins[7])) if 7 in ins else {}
                if 2 in meta:
                    out[(program, text(ins[1]))] = text(meta[2])
    return out


def _program(modules, starts, t):
    """The (start, end, name) of the module event running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i] if i >= 0 and t < modules[i][1] else None


def reduce_planes(planes) -> dict:
    """``{program: {scope path: seconds}}`` over planes as ``read_planes``
    gives them: those of ``trace_reduce.read_planes``, with a fourth item,
    the scope path, on each event of a device's ``XLA Ops`` line."""
    windows, devices = [], []
    for pname, lines in planes:
        if pname.startswith("/device:") and "TPU" in pname:
            devices.append(dict(lines))
        elif pname.startswith("/host:"):
            windows += [(s, s + d) for _, events in lines
                        for name, s, d in events if name == T.WINDOW]
    if not windows:
        raise ValueError(f"no '{T.WINDOW}' annotation in the trace")
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)

    out = defaultdict(lambda: defaultdict(float))
    for dev in devices:
        modules = sorted((s, s + d, T.program_name(n))
                         for n, s, d in dev.get(T.MODULES_LINE, []))
        starts = [m[0] for m in modules]
        # per program: path -> intervals of its ops and those below it
        spans = defaultdict(lambda: defaultdict(list))
        for _, s, d, path in dev.get(T.OPS_LINE, []):
            module = _program(modules, starts, s)
            iv = T._clip([(s, s + d)], lo, hi)
            if module is None or not iv:
                continue
            prog = spans[module[2]]
            parts = path.split("/") if path else [UNSCOPED]
            for k in range(1, len(parts) + 1):
                prog["/".join(parts[:k])] += iv
        for program, prog in spans.items():
            bare = prog.pop(UNSCOPED, [])
            scoped = [iv for path, ivs in prog.items() if "/" not in path
                      for iv in ivs]
            for path, ivs in prog.items():
                out[program][path] += _length(ivs) / 1e9
            out[program][UNSCOPED] += (_length(scoped + bare)
                                       - _length(scoped)) / 1e9
    n = len(devices)
    return {p: {k: v / n for k, v in sorted(d.items())}
            for p, d in out.items()}


def read_planes(path: pathlib.Path):
    """The planes of ``trace_reduce.read_planes``, each event of a
    device's ``XLA Ops`` line with its scope path as a fourth item."""
    from jax.profiler import ProfileData

    raw = pathlib.Path(path).read_bytes()
    names = op_names(raw)
    planes = []
    for p in ProfileData.from_serialized_xspace(raw).planes:
        lines = [(l.name, [(e.name, e.start_ns, e.duration_ns)
                           for e in l.events]) for l in p.lines]
        modules = sorted((s, s + d, n) for ln, events in lines
                         if ln == T.MODULES_LINE for n, s, d in events)
        starts = [m[0] for m in modules]

        def scoped(n, s, d):
            module = _program(modules, starts, s)
            pid = re.search(r"\((\d+)\)$", module[2]) if module else None
            key = (int(pid.group(1)) if pid else None,
                   T.op_name(n).lstrip("%"))
            return (n, s, d, scope_path(names.get(key, "")))

        planes.append((p.name, [
            (ln, [scoped(*e) for e in events] if ln == T.OPS_LINE
             else events) for ln, events in lines]))
    return planes


def reduce_trace(path: pathlib.Path) -> dict:
    return reduce_planes(read_planes(path))


def of_run(rec: dict, trace: dict | None, reader_file: str) -> dict | None:
    """The scope split of a traced run, or None for an untraced one.
    ``reader_file`` is the calling reader's ``__file__``: the run's trace
    lies under the ``bench/out/trace/<cell>`` beside it."""
    if not trace:
        return None
    if "scopes" not in trace:
        bench = pathlib.Path(reader_file).resolve().parents[1]
        trace["scopes"] = reduce_trace(T.find_trace(
            bench / "out" / "trace" / rec["cell"]))
    return trace["scopes"]


def ms_per(rec: dict, trace: dict | None, reader_file: str, program: str,
           scope: str) -> float | None:
    """Milliseconds of ``scope`` in ``program`` per request of the traced
    waves that program served; None without a trace or the scope."""
    s = (of_run(rec, trace, reader_file) or {}).get(program, {}).get(scope)
    n = sum(w["n"] for w in rec[WAVES[program]])
    return s * 1e3 / n if s and n else None
