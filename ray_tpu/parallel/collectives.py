"""What a compiled program puts on the interconnect.

``collectives(compiled.as_text())`` lists the collective operations of
an optimized HLO module, one row for each (operation, replica groups,
result shape) with its count and the megabytes of one result buffer a
device; ``format_collectives`` prints the rows.  The text can come from
a program compiled for a described topology (``tests/test_chip_compile
.py``), so the listing costs no chip time.
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

    def dims(self):
        """Every dimension of every result buffer."""
        return [int(n) for _, ns in _ARRAY.findall(self.shape) for n in ns.split(",") if n]


def collectives(hlo_text: str) -> list[Collective]:
    """The collectives of an optimized HLO module, largest total first.
    An asynchronous pair counts once, at its ``-start``, by the buffer
    it produces (an ``all-gather-start`` or ``collective-permute-start``
    carries its operand in the result tuple too)."""
    seen: Counter = Counter()
    for line in hlo_text.splitlines():
        m = _OP.search(line)
        if m is None:
            continue
        arrays = _ARRAY.findall(m["result"])
        if m["start"] and m["op"] in ("all-gather", "collective-permute"):
            arrays = arrays[1:2]
        groups = _GROUPS.search(line)
        shape = "+".join(f"{t}[{ns}]" for t, ns in arrays)
        seen[(m["op"], groups[1] if groups else "", shape)] += 1
    rows = []
    for (op, groups, shape), count in seen.items():
        size = sum(_BYTES[t] * math.prod(int(n) for n in ns.split(",") if n)
                   for t, ns in _ARRAY.findall(shape))
        rows.append(Collective(op, groups, shape, count, size / 1e6))
    return sorted(rows, key=lambda r: -r.count * r.mb)


def format_collectives(rows: list[Collective]) -> str:
    lines = [f"{'operation':<20} {'groups':<24} {'count':>5} {'MB each':>9} {'MB':>9}  shape"]
    for r in rows:
        buffers = r.shape.split("+")
        shape = buffers[0] if len(buffers) == 1 else f"{len(buffers)} buffers: {buffers[0]}, ..."
        lines.append(f"{r.op:<20} {r.groups:<24} {r.count:>5} {r.mb:>9.2f} {r.count * r.mb:>9.1f}  {shape}")
    lines.append(f"{'total':<20} {'':<24} {sum(r.count for r in rows):>5} {'':>9} "
                 f"{sum(r.count * r.mb for r in rows):>9.1f}")
    return "\n".join(lines)
