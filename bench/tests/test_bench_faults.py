"""A run's ``correct``: true for the program, false for the control and
for each fault a cell can have, planted under the timed path.

The chip check is skipped; everything else of a run is driven on a tiny
copy of the cell on the CPU.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench_tiny import harness, run, tiny_root

from bench.control import control_program


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def _ops(root, cell):
    return harness.Cell(root, cell).kind.OPS


def _altered(out):
    """The answer with one value changed where it is produced."""
    if isinstance(out, tuple):
        return (_altered(out[0]),) + out[1:]
    out = np.array(out, copy=True)
    if out.size == 0:
        return np.array([7], np.int64)
    out[0] += 1
    return out


def stale(op):
    """Each request gets the previous request's answer: state that a
    step leaves unchanged."""
    last = []

    def f(store, **args):
        out = op(store, **args)
        last.append(out)
        return last[-2] if len(last) > 1 else out
    return f


def altered(op):
    return lambda store, **args: _altered(op(store, **args))


def half_batch(op):
    """Half of the batch left out."""
    def f(store, ids):
        return op(store, ids=ids[:len(ids) // 2])
    return f


def raising(op):
    """Every third request past the warm-up raises: an answer that
    never comes."""
    calls = []

    def f(store, **args):
        calls.append(1)
        if len(calls) > 20 and len(calls) % 3 == 0:
            raise RuntimeError("planted")
        return op(store, **args)
    return f


@pytest.mark.parametrize("cell", ["lj"])
def test_program_is_correct(root, cell):
    out = run(root, cell)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"] == {"mismatched": {"value": 0, "limit": 0},
                             "failed": {"value": 0, "limit": 0}}
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"requests_per_s", "p50_ms", "p95_ms",
                                   "setup_s"}


@pytest.mark.parametrize("cell,fault", [
    ("lj", stale), ("lj", altered), ("lj", half_batch), ("lj", raising)])
def test_fault_is_not_correct(root, cell, fault):
    ops = {name: fault(op) for name, op in _ops(root, cell).items()}
    out = run(root, cell, ops=ops)
    assert not out["correct"], out
    assert (out["checks"]["mismatched"]["value"]
            + out["checks"]["failed"]["value"]) > 0


@pytest.mark.parametrize("cell", ["lj"])
def test_control_is_not_correct(root, cell):
    build, ops = control_program(harness.Cell(root, cell))
    out = run(root, cell, build=build, ops=ops, counters=dict)
    assert not out["correct"], out
    assert out["checks"]["mismatched"]["value"] > 0


def test_window_never_repeats_a_request(root):
    """The window draws each request afresh from the seed, so however
    fast the program answers, no list of ids comes twice."""
    seen = []

    def record(op):
        def f(store, ids):
            seen.append(tuple(np.asarray(ids).tolist()))
            return op(store, ids=ids)
        return f

    ops = {name: record(op) for name, op in _ops(root, "lj").items()}
    out = run(root, "lj", ops=ops)
    assert out["correct"], out
    window = seen[-out["attempted"]:]
    assert len(window) == out["attempted"] > 1
    assert len(set(window)) == len(window)
