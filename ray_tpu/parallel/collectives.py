"""What a compiled program puts on the interconnect.

``collectives(compiled.as_text())`` lists the collective operations of
an optimized HLO module, one row for each (operation, replica groups,
result shape) with its count, how many of those start asynchronously
and the megabytes of one result buffer a device; ``format_collectives``
prints the rows.  The text can come from a program compiled for a
described topology (``tests/test_chip_compile.py``), so the listing
costs no chip time.
"""

from __future__ import annotations

import math
import re
from collections import Counter
from typing import NamedTuple

_OP = re.compile(
    r" = (?P<result>\(.*?\)|\S+) "
    r"(?P<op>all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)(?P<start>-start)?\("
)
# a computation's first line, and a fusion with the computation it calls
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>[^\s(]+) \(.*\{$")
_FUSION = re.compile(r"^\s*(?:ROOT )?%(?P<name>\S+) = .*? fusion\(.*?\bcalls=%(?P<calls>[^\s,)]+)")
_ASYNC_FUSION = "async-collective-start"
_GROUPS = re.compile(r"(?:replica_groups|source_target_pairs)=(\{\{.*?\}\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")
_ARRAY = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


class Collective(NamedTuple):
    op: str
    groups: str
    shape: str  # the result buffers, layouts dropped: "bf16[4,1024,1280]" or a "+"-joined tuple
    count: int
    mb: float  # of one operation's result on a device
    started_async: int = 0  # of `count`: those another instruction may run beside

    def dims(self):
        """Every dimension of every result buffer."""
        return [int(n) for _, ns in _ARRAY.findall(self.shape) for n in ns.split(",") if n]


def collectives(hlo_text: str) -> list[Collective]:
    """The collectives of an optimized HLO module, largest total first.
    An asynchronous pair counts once, at its ``-start``, by the buffer
    it produces (an ``all-gather-start`` or ``collective-permute-start``
    carries its operand in the result tuple too).  The TPU compiler's
    asynchronous fusion repeats its collective in every fused
    computation from ``async-collective-start`` to ``-done`` (the fusions
    it runs beside among them): it counts once as well, in the
    computation the start calls; a collective in any other fused
    computation is such a repeat and is not counted."""
    lines = hlo_text.splitlines()
    # fused computation -> does an `async-collective-start` fusion call it
    fused = {m["calls"]: m["name"].startswith(_ASYNC_FUSION)
             for m in map(_FUSION.match, lines) if m is not None}
    seen: Counter = Counter()
    started: Counter = Counter()
    in_async_fusion = None  # of the computation being read; None outside a fused one
    for line in lines:
        head = _COMPUTATION.match(line)
        if head is not None:
            in_async_fusion = fused.get(head["name"])
            continue
        m = _OP.search(line)
        if m is None or in_async_fusion is False:
            continue
        arrays = _ARRAY.findall(m["result"])
        if m["start"] and m["op"] in ("all-gather", "collective-permute"):
            arrays = arrays[1:2]
        groups = _GROUPS.search(line)
        shape = "+".join(f"{t}[{ns}]" for t, ns in arrays)
        key = (m["op"], groups[1] if groups else "", shape)
        seen[key] += 1
        started[key] += bool(m["start"] or in_async_fusion)
    rows = []
    for (op, groups, shape), count in seen.items():
        size = sum(_BYTES[t] * math.prod(int(n) for n in ns.split(",") if n)
                   for t, ns in _ARRAY.findall(shape))
        rows.append(Collective(op, groups, shape, count, size / 1e6, started[op, groups, shape]))
    return sorted(rows, key=lambda r: -r.count * r.mb)


def format_collectives(rows: list[Collective]) -> str:
    lines = [f"{'operation':<20} {'groups':<24} {'count':>5} {'async':>5} {'MB each':>9} {'MB':>9}  shape"]
    for r in rows:
        buffers = r.shape.split("+")
        shape = buffers[0] if len(buffers) == 1 else f"{len(buffers)} buffers: {buffers[0]}, ..."
        lines.append(f"{r.op:<20} {r.groups:<24} {r.count:>5} {r.started_async:>5} {r.mb:>9.2f} "
                     f"{r.count * r.mb:>9.1f}  {shape}")
    lines.append(f"{'total':<20} {'':<24} {sum(r.count for r in rows):>5} "
                 f"{sum(r.started_async for r in rows):>5} {'':>9} "
                 f"{sum(r.count * r.mb for r in rows):>9.1f}")
    return "\n".join(lines)
