"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer readers take.

The measured window is the host span ``bench.window`` the harness records;
where it is missing, the span of the device events.  Per TPU device plane
(``/device:TPU:<n>``), inside the window:

* ``busy``: the union of the intervals of the ``XLA Ops`` line's events;
* ``modules``: the device time of each compiled program (``XLA Modules``
  line), by the jitted function's name (``jit_exec_chunk(17)`` ->
  ``exec_chunk``);
* ``collectives``: the device time of collective operations
  (collective-permute, all-reduce, all-gather, reduce-scatter, all-to-all),
  and the part of it during which no other operation ran on that device;
* ``breakdown``: the ten device operations that took most time on the first
  device, each by its own time (a ``while`` or ``cond`` contains the
  operations of its body on the same line; their time is theirs, not the
  container's), and its ten longest idle gaps, each named by the innermost
  host span that covers the gap's middle.

Times are seconds.  ``busy_s`` is averaged over the devices used.
"""

from __future__ import annotations

import pathlib
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a TPU op event is named by its HLO text, ``%collective-permute-start.3 =
# f32[...] ...``
COLLECTIVE = re.compile(r"^%?(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all)")
MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def union(intervals) -> list:
    """Merge (start, end) intervals -> sorted disjoint intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def module_name(event_name: str) -> str:
    return MODULE_NAME.match(event_name).group(1)


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def load(path) -> dict:
    """-> {"devices": {n: {line name: [(name, start, end)]}},
           "host": [(name, start, end)]} in nanoseconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            devices[int(match.group(1))] = {
                line.name: _events(line) for line in plane.lines
                if line.name in ("XLA Ops", "XLA Modules")}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(_events(line))
    return {"devices": devices, "host": host}


def reduce(raw: dict, chips: int) -> dict:
    """The numbers of a loaded trace (see the module docstring)."""
    devices = [raw["devices"][k] for k in sorted(raw["devices"])][:chips]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    spans = [(s, e) for n, s, e in raw["host"] if n == WINDOW_SPAN]
    if spans:
        lo, hi = spans[0]
    else:
        every = [ev for dev in devices for evs in dev.values() for ev in evs]
        lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)
    ns = 1e-9
    per_device = []
    for dev in devices:
        ops = [(n, s, e) for n, s, e in dev.get("XLA Ops", [])
               if e > lo and s < hi]
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        coll = [(s, e) for n, s, e in ops if COLLECTIVE.match(n)]
        other = union(clip([(s, e) for n, s, e in ops
                            if not COLLECTIVE.match(n)], lo, hi))
        coll_u = union(clip(coll, lo, hi))
        exposed = total(coll_u) - total(_intersect(coll_u, other))
        modules: dict = {}
        for n, s, e in dev.get("XLA Modules", []):
            part = total(clip([(s, e)], lo, hi))
            if part:
                key = module_name(n)
                modules[key] = modules.get(key, 0.0) + part * ns
        per_device.append({"busy": busy, "busy_s": total(busy) * ns,
                           "collective_s": total(clip(coll, lo, hi)) * ns,
                           "collective_exposed_s": exposed * ns,
                           "modules_s": modules,
                           "ops_s": self_times(ops, lo, hi)})
    first = per_device[0]
    edges = [lo] + [x for iv in first["busy"] for x in iv] + [hi]
    gaps = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s),
                  key=lambda g: g[0] - g[1])
    top_ops = sorted(first["ops_s"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "devices": len(per_device),
        "modules_s": first["modules_s"],
        "collective_s": first["collective_s"],
        "collective_exposed_s": first["collective_exposed_s"],
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": [[_host_at(raw["host"], (s + e) / 2),
                                     (e - s) * ns] for s, e in gaps[:10]]},
    }


def self_times(ops, lo, hi) -> dict:
    """Seconds of each op name inside [lo, hi], less the time of the ops
    nested in it: an op's parent is the innermost earlier op that contains
    it (ops that only overlap are not nested)."""
    own: dict = {}
    stack: list = []            # [name, start, end, children's intervals]

    def finish(entry):
        n, s, e, children = entry
        part = total(clip([(s, e)], lo, hi)) - total(union(clip(children,
                                                                lo, hi)))
        own[n] = own.get(n, 0.0) + part * 1e-9

    for n, s, e in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][2] <= s:
            finish(stack.pop())
        parent = next((entry for entry in reversed(stack) if e <= entry[2]),
                      None)
        if parent is not None:
            parent[3].append((s, e))
        stack.append([n, s, e, []])
    for entry in reversed(stack):
        finish(entry)
    return own


def _intersect(a, b) -> list:
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


def _host_at(host, t) -> str:
    """The innermost host span (shortest, non-empty) that covers ``t``."""
    best = None
    for n, s, e in host:
        if s <= t <= e and e > s and n != WINDOW_SPAN:
            if best is None or e - s < best[1]:
                best = (n, e - s)
    return best[0] if best else "no host span"


def reduce_dir(directory, chips: int) -> dict:
    files = sorted(pathlib.Path(directory).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce(load(files[-1]), chips)
