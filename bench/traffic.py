"""The one traffic generator: turns a mix file of ``bench/traffic/`` and a
seed into each client's endless stream of (query, binding) requests.

A mix file holds only parameters:

* ``loop``: ``"closed"`` (a client sends its next request when the answer
  to its last one has come);
* ``clients``: how many clients;
* ``max_batch``: the server's micro-batch limit;
* ``shapes``: which query each request runs.  ``{"rounds": [q, ...]}``
  sends the listed queries in rounds, each round in a shuffled order;
  ``{"weights": {q: p, ...}}`` draws each request's query with those
  probabilities;
* ``schedule_seed``: the seed of that sequence of queries.

The sequence of queries comes from ``schedule_seed`` and so is the same for
every ``--seed``: a seed changes the data and every binding, never how much
work a window holds.  Bindings come from ``--seed`` through each query's
own ``binding(rng)`` (its TPC-H substitution range).
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, Iterator, Tuple

import numpy as np

Request = Tuple[str, Dict[str, object]]


def shape_stream(shapes: dict, schedule_seed: int, client: int) -> Iterator[str]:
    rng = np.random.default_rng([schedule_seed, client])
    if "rounds" in shapes:
        names = list(shapes["rounds"])
        while True:
            yield from (names[i] for i in rng.permutation(len(names)))
    names = sorted(shapes["weights"])
    p = np.array([shapes["weights"][n] for n in names], np.float64)
    if abs(p.sum() - 1.0) > 1e-9:
        raise ValueError(f"query weights sum to {p.sum()}, not 1")
    while True:
        yield names[int(rng.choice(len(names), p=p))]


def queries_of(mix: dict) -> Tuple[str, ...]:
    shapes = mix["shapes"]
    return tuple(sorted(shapes["rounds"] if "rounds" in shapes else shapes["weights"]))


class Clients:
    """Each client's request stream for one run."""

    def __init__(self, mix: dict, seed: int, binding: Callable[[str, object], dict]):
        if mix["loop"] != "closed":
            raise ValueError(f"unsupported loop {mix['loop']!r}")
        self.n = int(mix["clients"])
        self._streams = [
            zip(
                shape_stream(mix["shapes"], int(mix["schedule_seed"]), c),
                itertools.repeat(np.random.default_rng([seed, c])),
            )
            for c in range(self.n)
        ]
        self._binding = binding

    def next(self, client: int) -> Request:
        qname, rng = next(self._streams[client])
        return qname, self._binding(qname, rng)
