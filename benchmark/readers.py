"""The readers of per-layer metrics.

A per-layer metric is a file ``layer_metrics/<metric>.json`` that names
one of these readers and gives it arguments.  A reader gets the run's
context and returns a number, or None where it finds nothing to read;
the harness then leaves the metric out of the line.

Context keys: ``values`` (numbers the runner computed), ``stats``
(``before`` and ``after``: ``LLMEngine.stats()`` at the ends of the
window, and ``window_s``), ``trace`` (``trace_reduce.reduce``'s facts),
``sizes``, ``job`` (the cell's ``job`` or ``traffic`` parameters),
``chips``, ``peak`` (the row of ``peaks.json`` for this device).
"""

from __future__ import annotations

import ast
import operator
import re

from benchmark import flops

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
        ast.Div: operator.truediv}


def _arith(expr: str, names: dict):
    """Evaluate +, -, *, / over numbers and dotted names (``d.steps``)."""

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return node.value
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        if isinstance(node, ast.Name):
            return names[node.id]
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
            return names[node.value.id][node.attr]
        raise ValueError(f"not arithmetic: {ast.dump(node)}")

    return ev(ast.parse(expr, mode="eval"))


def runner_value(args, ctx):
    """A number the runner computed: ``{"key": ..., "scale": 1}``."""
    v = ctx["values"].get(args["key"])
    return None if v is None else v * args.get("scale", 1)


def stats_delta(args, ctx):
    """Arithmetic over the engine's counters: ``{"expr": ...}`` with
    ``d.<key>`` the change of a counter over the window, ``s.<key>`` its
    value after it, ``v.<key>`` a runner value and ``window_s``."""
    stats = ctx.get("stats")
    if not stats:
        return None
    before, after = stats["before"], stats["after"]
    delta = {k: after[k] - before[k] for k in after
             if isinstance(after[k], (int, float)) and isinstance(before.get(k), (int, float))}
    try:
        return _arith(args["expr"], {"d": delta, "s": after, "v": ctx["values"],
                                     "window_s": stats["window_s"]})
    except (KeyError, ZeroDivisionError):
        return None


def _matching_seconds(table: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(s for name, s in table.items() if rx.search(name))


def _steps(trace):
    return trace.get("span_counts", {}).get("step") or None


def trace_ops(args, ctx):
    """Device time of operations of device 0 whose name matches
    ``pattern``.  ``mode``: ``ms_per_step`` (over the ``step``
    annotations traced), ``pct_of_busy``, or ``roofline_pct``: the least
    time the chip could take for ``work`` (a function of ``flops.py``
    over sizes, batch and seq, divided among the chips) over the time
    measured."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices"):
        return None
    seconds = _matching_seconds(trace["op_seconds"], args["pattern"])
    mode = args["mode"]
    if mode == "pct_of_busy":
        return 100.0 * seconds / trace["busy_s_device0"]
    steps = _steps(trace)
    if not steps or (mode == "roofline_pct" and seconds == 0):
        return None
    if mode == "ms_per_step":
        return 1000.0 * seconds / steps
    if mode == "roofline_pct":
        work = getattr(flops, args["work"])(ctx["sizes"], ctx["job"]["batch"], ctx["job"]["seq"])
        share = {k: v / ctx["chips"] for k, v in work.items()}
        return 100.0 * flops.least_seconds(share, ctx["peak"])["seconds"] / (seconds / steps)
    raise ValueError(f"trace_ops: unknown mode {mode!r}")


def trace_idle(args, ctx):
    """Share of the traced window in which no operation ran on device 0."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices"):
        return None
    return 100.0 * (1.0 - trace["busy_s_device0"] / trace["window_s"])


def trace_span(args, ctx):
    """Host time inside TraceAnnotations whose name matches ``pattern``,
    as a share of the traced window."""
    trace = ctx.get("trace")
    if not trace or not trace.get("span_seconds"):
        return None
    return 100.0 * _matching_seconds(trace["span_seconds"], args["pattern"]) / trace["window_s"]


READERS = {f.__name__: f for f in (runner_value, stats_delta, trace_ops, trace_idle, trace_span)}
