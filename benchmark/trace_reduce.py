"""From a profiler trace to numbers.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  `load` turns
it into plain lists, `reduce` turns those into what the readers need:
busy seconds of each device (the union of the intervals in which an
operation ran), time by operation family (the name less its instruction
number), the longest idle gaps of device 0
labelled by the benchmark's own host annotations, and host time inside
annotations.  `reduce` works on the plain lists, so a small recorded
trace kept as JSON checks it on a box with no chip.
"""

from __future__ import annotations

import glob
import os
import re

# a device plane: "/device:TPU:0"; its operations are on the line "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# lines of a device plane that hold whole programs or steps, not operations
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops", "Framework Name Scope",
           "Source code")


_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line,
    ``%fusion.26 = (f32[...]) fusion(...), kind=kOutput, ...``.  Keep the
    instruction's own name, and for a custom call its target, which is
    what tells a Pallas kernel (``tpu_custom_call``) from the rest:
    ``fusion.26``, ``attn.103 tpu_custom_call``.  Any other name (a host
    annotation, a module) stays as it is."""
    if not name.startswith("%") or " = " not in name:
        return name
    head, rest = name[1:].split(" = ", 1)
    op = _OPCODE.search(rest)
    if op and op.group(1) == "custom-call":
        target = _TARGET.search(rest)
        return f"{head} {target.group(1)}" if target else f"{head} custom-call"
    return head


def family(name: str) -> str:
    """An operation's name without its instruction numbers, which change
    from compile to compile: ``fusion.26`` -> ``fusion``,
    ``copy.1610.remat`` -> ``copy.remat``,
    ``attn.103 tpu_custom_call`` -> ``attn tpu_custom_call``."""
    head, _, target = name.partition(" ")
    head = re.sub(r"\.\d+(?=\.|$)", "", head)
    return f"{head} {target}" if target else head


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> list:
    """[{"name": plane, "lines": [{"name": line, "events": [[name, start_ns, dur_ns], ...]}]}]"""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_seconds(intervals) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_ops(planes: list) -> dict:
    """{device id: [[name, start_ns, dur_ns], ...]} of operations."""
    out = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
        if not lines:
            lines = [ln for ln in plane["lines"] if ln["name"] not in NOT_OPS]
        out[int(m.group(1))] = [e for ln in lines for e in ln["events"] if e[2] > 0]
    return out


def host_spans(planes: list, names) -> list:
    """[[name, start_ns, dur_ns]] of host events whose name is one of
    `names` (the benchmark's TraceAnnotations), from every host plane."""
    names = set(names)
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[0] in names)
    return out


def _clipped(events, end):
    """The events that start before `end`, none reaching beyond it."""
    return [[n, s, min(d, end - s)] for n, s, d in events if s < end]


def reduce(planes: list, annotations=(), top: int = 10, first_s=None) -> dict:
    """The facts of one traced window.  Device 0 is the lowest device id
    present.  The window runs from the first operation to the end of the
    last on any device, so idle time before the first dispatch and after
    the last is not in it; with `first_s`, it ends that many seconds
    after the first operation and what the trace holds beyond is cut."""
    ops = device_ops(planes)
    if not ops or not any(ops.values()):
        return {"devices": 0}
    start = min(e[1] for evs in ops.values() for e in evs)
    spans = host_spans(planes, annotations)
    if first_s is not None:
        cut = start + int(first_s * 1e9)
        ops = {d: _clipped(evs, cut) for d, evs in ops.items()}
        spans = _clipped(spans, cut)
    end = max(e[1] + e[2] for evs in ops.values() for e in evs)
    busy = {d: union_seconds((e[1], e[1] + e[2]) for e in evs) / 1e9 for d, evs in ops.items()}
    dev0 = min(ops)
    by_name, count = {}, {}
    for name, _s, dur in ops[dev0]:
        name = family(name)
        by_name[name] = by_name.get(name, 0) + dur
        count[name] = count.get(name, 0) + 1
    gaps = []
    merged = _merged((e[1], e[1] + e[2]) for e in ops[dev0])
    edges = [[start, start]] + merged + [[end, end]]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 > e0:
            mid = (e0 + s1) // 2
            label = next((n for n, s, d in spans if s <= mid < s + d), "unattributed")
            gaps.append([label, (s1 - e0) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    span_s = {}
    for name, _s, dur in spans:
        span_s[name] = span_s.get(name, 0.0) + dur / 1e9
    return {
        "devices": len(ops),
        "window_s": (end - start) / 1e9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_device0": busy[dev0],
        "op_seconds": {n: d / 1e9 for n, d in sorted(by_name.items(), key=lambda kv: -kv[1])},
        "op_counts": count,
        "idle_gaps": gaps[:top],
        "idle_gap_count": len(gaps),
        "span_seconds": span_s,
        "span_counts": {n: sum(1 for e in spans if e[0] == n) for n in span_s},
    }


def breakdown(facts: dict, top: int = 10) -> dict:
    ops = list(facts.get("op_seconds", {}).items())[:top]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": facts.get("idle_gaps", [])[:top]}
