"""Plain reference for store kind ``powerlaw_filter``.

Straight numpy over the generated edge list and label columns: it
imports nothing of the program and reads nothing the program made.  A
predicate is the configuration's text, evaluated with numpy's boolean
operators on the label columns (``data.labels``); the answer to
``filter<i>`` is the unfiltered answer of ``powerlaw_ref`` (the sorted
union of the batch's ``dst``) masked by predicate ``i``.  ``fanout``
passes through to ``powerlaw_ref.retrieve`` for the control.
"""
from __future__ import annotations

import ast
import types
from typing import Callable, Iterable, Optional

import numpy as np

from bench.kinds import powerlaw_ref

#: predicates a configuration of this kind lists, one op each
N_PREDICATES = 4


def predicate(text: str, leaf: Callable):
    """The predicate ``text`` -- ``L("name")`` leaves combined by ``&``,
    ``|`` and ``~`` with Python's precedence -- built bottom up:
    ``leaf(name)`` for each leaf, the operator of the values for each
    node."""
    def walk(node):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitAnd):
            return walk(node.left) & walk(node.right)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitOr):
            return walk(node.left) | walk(node.right)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Invert):
            return ~walk(node.operand)
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "L" and len(node.args) == 1
                and not node.keywords
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            return leaf(node.args[0].value)
        raise ValueError(f"not a label predicate: {ast.unparse(node)!r}")
    return walk(ast.parse(text, mode="eval").body)


def prepare(cfg: dict, data,
            requests: Optional[Iterable]) -> types.SimpleNamespace:
    """``powerlaw_ref.prepare``, with each predicate's vertex mask."""
    ref = powerlaw_ref.prepare(cfg, data, requests)
    ref.masks = [predicate(text, lambda name: data.labels[name])
                 for text in cfg["predicates"]]
    return ref


def _filtered(i: int):
    def op(ref, ids: np.ndarray, fanout: int | None = None) -> np.ndarray:
        ans = powerlaw_ref.retrieve(ref, ids, fanout)
        return ans[ref.masks[i][ans]]
    return op


OPS = {f"filter{i}": _filtered(i) for i in range(N_PREDICATES)}
