"""Reduction of a profiler trace to the program's own names: device time
per ``repro.*`` scope and host self time per ``repro.*`` span.

    python3 bench/scopes.py <trace dir> --steps <n>

prints the reduction of the newest ``.xplane.pb`` under the directory as
one JSON line.  The names are the constants of ``src/repro/core/spans.py``
(a test holds the two lists equal); they are written out here so that this
module reads a trace of any version of the program, which then simply has
none of them.

Device.  The ``XLA Ops`` line of the first TPU device plane.  An op's path
is the ``op_name`` of its HLO instruction's metadata, e.g.
``jit(exec_chunk)/while/body/closed_call/repro.grad/vmap(jvp())/dot``.  The
TPU's op events carry no such stat (their stats are the device offset and
duration), so the path is looked up by instruction name in the compiled
program the op ran in: the ``XLA Modules`` event around it names the
program, and the trace's ``/host:metadata`` plane holds each program's
optimized ``HloProto`` (under the stat ``Hlo Proto``), which
``hlo_op_names`` reads with a small protobuf reader, as ``ProfileData``
does not expose it.
An op belongs to the outermost ``repro.*`` component of its path, so a
gradient inside the snapshot refresh is snapshot time.  XLA Ops nest
(``while`` and ``cond`` contain their bodies), so each scope's time is the
union of its ops' intervals, clipped to the window; ``unscoped_s`` is the
busy time (the union of every op) that no scope covers.

Host.  The ``repro.*`` spans of every host line.  A span's self time is
its duration inside the window less that of the ``repro.*`` spans nested
in it (a history ``repro.pull`` inside ``repro.dispatch``).  ``span_args``
sums each span's numeric arguments but ``run`` (the job's number), over
the spans that start inside the window; ``span_count`` counts them.
``idle_s`` splits the device's idle time (the window less busy) by the span
whose self time it falls in; ``idle_unspanned_s`` is the idle time that no
span covers.

The window is the harness's ``bench.window`` host span; without it, the
span of the device's ops.  Times are seconds.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

SCOPES = ("repro.sample", "repro.grad", "repro.snapshot", "repro.update",
          "repro.mix", "repro.prox", "repro.fused_update", "repro.record")
SPANS = ("repro.plan", "repro.stage", "repro.dispatch", "repro.pull",
         "repro.ckpt")
PREFIX = "repro."
HLO_STAT = "Hlo Proto"
RUN_ARG = "run"
WINDOW_SPAN = "bench.window"

NS = 1e-9


def _union(intervals) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _intersect(a, b) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _minus(a, b, lo, hi) -> list:
    """``a`` less ``b`` (sorted disjoint lists inside [lo, hi])."""
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    rest = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return _intersect(a, rest)


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: an int for varints,
    bytes for length-delimited and fixed-width fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def hlo_op_names(xspace: bytes) -> dict:
    """``{program: {instruction: op_name}}`` from the ``Hlo Proto`` stats
    of the ``/host:metadata`` plane of a serialized XSpace.  A program is
    named as the ``XLA Modules`` events name it (``jit_exec_chunk(12)``).
    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (a map
    entry: key 1, value 2), .stat_metadata 5; XEventMetadata.name 2, .stats
    5; XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out: dict = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        if next((v for f, v in _fields(plane) if f == 2), b"") \
                != b"/host:metadata":
            continue
        stat_names = {}
        for f, entry in _fields(plane):
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1)] = meta.get(2, b"").decode()
        for f, entry in _fields(plane):
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            program = next((v for k, v in meta if k == 2), b"").decode()
            for k, stat in meta:
                stat = dict(_fields(stat)) if k == 5 else {}
                if stat_names.get(stat.get(1)) != HLO_STAT or 6 not in stat:
                    continue
                names = out.setdefault(program, {})
                module = dict(_fields(stat[6])).get(1, b"")
                for f3, comp in _fields(module):
                    for f4, instr in (_fields(comp) if f3 == 3 else ()):
                        if f4 != 2:
                            continue
                        parts = dict(_fields(instr))
                        op_name = dict(_fields(parts.get(7, b""))).get(2)
                        if op_name:
                            names[parts[1].decode()] = op_name.decode()
    return out


def _instruction(event_name: str) -> str:
    """``%copy.415 = f32[...] copy(...)`` -> ``copy.415``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def events(path, keep_ops=None) -> dict:
    """The events this reduction reads, as plain lists: ``device`` holds
    ``[name, start_ns, end_ns, path]`` of the first TPU plane's XLA Ops,
    ``host`` holds ``[name, start_ns, end_ns, stats, line]`` of the host
    spans named ``repro.*`` or ``bench.window``.  ``keep_ops`` (a predicate
    on one device event) thins the device list, for fixtures."""
    import re
    import warnings

    from jax.profiler import ProfileData
    device_plane = re.compile(r"^/device:TPU:(\d+)$")
    raw = pathlib.Path(path).read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    tpus, host = {}, []
    with warnings.catch_warnings():
        # the stats' iterator type has no __module__ attribute, which
        # Python reports as a DeprecationWarning when it is first made
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            match = device_plane.match(plane.name)
            if match:
                tpus[int(match.group(1))] = plane
            elif plane.name.startswith("/host:CPU"):
                for i, line in enumerate(plane.lines):
                    for ev in line.events:
                        if ev.name.startswith(PREFIX) \
                                or ev.name == WINDOW_SPAN:
                            host.append([ev.name, ev.start_ns, ev.end_ns,
                                         dict(ev.stats), i])
    device = []
    if tpus:
        lines = {line.name: sorted(((ev.start_ns, ev.end_ns, ev.name)
                                    for ev in line.events))
                 for line in tpus[min(tpus)].lines}
        programs = lines.get("XLA Modules", [])
        names = hlo_op_names(raw)
        # a program the metadata names otherwise (another compile id) is
        # looked up by its function's name
        by_function: dict = {}
        for program, ops in names.items():
            by_function.setdefault(program.split("(")[0], {}).update(ops)
        j = 0
        for s, e, name in lines.get("XLA Ops", []):
            while j < len(programs) and programs[j][1] < s:
                j += 1
            program = programs[j][2] if j < len(programs) \
                and programs[j][0] <= s else ""
            ops = names.get(program) or by_function.get(
                program.split("(")[0], {})
            device.append([name, s, e, ops.get(_instruction(name), "")])
    if keep_ops is not None:
        device = [ev for ev in device if keep_ops(ev)]
    return {"device": device, "host": host}


def scope_of(path: str) -> str | None:
    """The outermost ``repro.*`` component of an op's path."""
    for part in path.split("/"):
        if part.startswith(PREFIX):
            return part
    return None


def reduce(ev: dict) -> dict:
    """``events()`` -> the seconds and sums of the module docstring."""
    window = [(s, e) for n, s, e, _, _ in ev["host"] if n == WINDOW_SPAN]
    ops = [(path, s, e) for _, s, e, path in ev["device"]]
    if window:
        lo, hi = window[0]
    elif ops:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    else:
        lo = hi = 0
    by_scope: dict = {}
    for path, s, e in ops:
        by_scope.setdefault(scope_of(path), []).append((s, e))
    scoped = [iv for k, ivs in by_scope.items() if k for iv in ivs]
    busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
    covered = _total(_union(_clip(scoped, lo, hi)))
    scopes_s = {k: _total(_union(_clip(ivs, lo, hi))) * NS
                for k, ivs in by_scope.items() if k}
    idle = _minus([(lo, hi)] if hi > lo else [], busy, lo, hi)

    spans = [(n, s, e, stats, line) for n, s, e, stats, line in ev["host"]
             if n.startswith(PREFIX)]
    spans_s: dict = {}
    span_args: dict = {}
    span_count: dict = {}
    idle_s: dict = {}
    for n, s, e, stats, line in spans:
        nested = _union(_clip(
            [(s2, e2) for n2, s2, e2, _, line2 in spans
             if line2 == line and s <= s2 and e2 <= e
             and (s2, e2) != (s, e)], lo, hi))
        own = _minus(_clip([(s, e)], lo, hi), nested, lo, hi)
        spans_s[n] = spans_s.get(n, 0.0) + _total(own) * NS
        idle_s[n] = idle_s.get(n, 0.0) + _total(_intersect(idle, own)) * NS
        if lo <= s < hi:
            span_count[n] = span_count.get(n, 0) + 1
            sums = span_args.setdefault(n, {})
            for k, v in stats.items():
                if k != RUN_ARG and isinstance(v, (int, float)):
                    sums[k] = sums.get(k, 0) + v
    spanned = _union(_clip([(s, e) for _, s, e, _, _ in spans], lo, hi))
    return {"window_s": (hi - lo) * NS, "busy_s": _total(busy) * NS,
            "scopes_s": scopes_s,
            "unscoped_s": (_total(busy) - covered) * NS,
            "spans_s": spans_s, "span_args": span_args,
            "span_count": span_count, "idle_s": idle_s,
            "idle_unspanned_s": _total(_minus(idle, spanned, lo, hi)) * NS}


def per_step(summary: dict, steps: int) -> dict:
    """The per-layer numbers these names give, per step of the window;
    a number whose scopes or spans the trace lacks is left out."""
    if not steps:
        return {}
    scopes, spans = summary["scopes_s"], summary["spans_s"]
    out = {}

    def us(names, source):
        if any(n in source for n in names):
            return 1e6 * sum(source.get(n, 0.0) for n in names) / steps
        return None

    out["plan_us_per_step"] = us(["repro.plan"], spans)
    out["stage_us_per_step"] = us(["repro.stage"], spans)
    out["dispatch_us_per_step"] = us(["repro.dispatch"], spans)
    moved = [v for args in summary["span_args"].values()
             for k, v in args.items() if k in ("h2d_bytes", "d2h_bytes")]
    out["transfer_kb_per_step"] = sum(moved) / steps / 1e3 if moved else None
    out["grad_us_per_step"] = us(["repro.grad", "repro.snapshot"], scopes)
    out["opt_us_per_step"] = us(["repro.update", "repro.mix", "repro.prox"],
                                scopes)
    return {k: v for k, v in out.items() if v is not None}


def newest(directory) -> pathlib.Path:
    files = sorted(pathlib.Path(directory).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("directory")
    ap.add_argument("--steps", type=int, required=True)
    args = ap.parse_args(argv)
    summary = reduce(events(newest(args.directory)))
    summary["per_step"] = per_step(summary, args.steps)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
