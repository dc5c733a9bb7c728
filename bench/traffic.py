"""The one traffic generator: a mix file of parameters -> request stream.

A mix (``bench/traffic/<name>.json``) lists ops, each with an integer
``share`` and the arguments it draws.  Requests come in blocks of
``sum(share)``: every block holds each op exactly ``share`` times, in an
order shuffled from the seed, so every seed issues the same mix and only
the order and the drawn ids differ.  Argument draws:

* ``{"draw": "uniform", "over": D}`` -- one id, uniform over domain ``D``;
* ``{"draw": "uniform_distinct", "count": k, "over": D}`` -- ``k``
  distinct ids, uniform over ``D``, in drawn order.

Domains (``vertices``, ...) and their sizes come from the
configuration's data.  The window draws its requests one at a time from
an endless stream, so it never repeats a list of requests however fast
the program answers; ``cover``, ``warmup`` and ``settle`` size the
warm-up (``bench/harness.py`` ``warm_up``), which draws from a stream of
its own.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

Request = Tuple[str, Dict[str, object]]

#: seed-stream tags: the window's requests and the warm-up's never share
#: draws
WINDOW, WARMUP = 1, 2


def _draw(spec: dict, domains: Dict[str, int], rng: np.random.Generator):
    n = int(domains[spec["over"]])
    kind = spec["draw"]
    if kind == "uniform":
        return int(rng.integers(0, n))
    if kind == "uniform_distinct":
        k = int(spec["count"])
        if k > n:
            raise ValueError(f"{k} distinct ids asked of a domain of {n}")
        return rng.choice(n, size=k, replace=False).astype(np.int64)
    raise ValueError(f"unknown draw {kind!r}")


def stream(mix: dict, domains: Dict[str, int], seed: int,
           tag: int) -> Iterator[Request]:
    """The endless request stream of ``mix`` drawn from ``(seed, tag)``."""
    rng = np.random.default_rng([int(seed), tag])
    ops = mix["ops"]
    block = [i for i, op in enumerate(ops) for _ in range(int(op["share"]))]
    while True:
        for i in rng.permutation(block):
            op = ops[int(i)]
            yield op["op"], {name: _draw(spec, domains, rng)
                             for name, spec in op["args"].items()}

