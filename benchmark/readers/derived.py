"""derived reader: arithmetic over values the run already has.

params: {"expr": "100 * a / b - c"}. Names the expression may use: the
cell's end-to-end metrics and the per-layer metrics read before this one,
`span_<name>` (median seconds of a benchmark span), the driver's facts, the
work quantities of `work.py`, `peak_<key>` from peaks.json, `mix_<key>` and
`cfg_<key>` for the numbers of the traffic and configuration files, and
`trace_busy_s` / `trace_window_s`. A name that is missing makes the metric
absent (returns None); only + - * / and parentheses are evaluated."""
import ast
import operator

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub,
        ast.Mult: operator.mul, ast.Div: operator.truediv}


class _Missing(Exception):
    pass


def _eval(node, names):
    if isinstance(node, ast.Expression):
        return _eval(node.body, names)
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return node.value
    if isinstance(node, ast.Name):
        if names.get(node.id) is None:
            raise _Missing(node.id)
        return names[node.id]
    if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
        return _OPS[type(node.op)](_eval(node.left, names),
                                   _eval(node.right, names))
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval(node.operand, names)
    raise ValueError(f"derived: {ast.dump(node)} is not arithmetic")


def read(params, ctx):
    try:
        return float(_eval(ast.parse(params["expr"], mode="eval"),
                           ctx["names"]))
    except _Missing:
        return None
