"""The one traffic generator: a mix's parameters and a seed give each
stream its sends.

A mix (``traffic/<mix>.json``) states the loop, the number of streams, the
order in which stream ``i`` cycles through its statements, and for every
statement the grid its substitution parameters are drawn from (whole-number
ranges, ends included). Every seed sends the SAME set of parameter draws,
the whole grid, in another order, so the seed moves no work; a stream that
outlasts a grid starts it again.
"""

from __future__ import annotations

import itertools

import numpy as np


def grid(spec: dict) -> list:
    """Every combination of a statement's parameters, in a fixed order."""
    names = sorted(spec)
    axes = [range(int(spec[n]["range"][0]), int(spec[n]["range"][1]) + 1)
            for n in names]
    return [dict(zip(names, combo)) for combo in itertools.product(*axes)]


class Schedule:
    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.streams = int(mix["streams"])
        if mix["loop"] != "closed":
            raise ValueError(f"loop {mix['loop']!r}: this generator sends "
                             "closed loops only")

    def order(self, stream: int) -> list:
        orders = self.mix["order"]
        return list(orders[stream % len(orders)])

    def _shuffled(self, stream: int, stmt: str) -> list:
        combos = grid(self.mix["statements"][stmt]["params"])
        which = sorted(self.mix["statements"]).index(stmt)
        rng = np.random.default_rng([self.seed, 0x7A, stream, which])
        return [combos[i] for i in rng.permutation(len(combos))]

    def sends(self, stream: int):
        """(statement, parameters) for ever: the stream's statements in
        its order, each walking its own shuffled grid round and round."""
        order = self.order(stream)
        cycles = {s: itertools.cycle(self._shuffled(stream, s))
                  for s in set(order)}
        for stmt in itertools.cycle(order):
            yield stmt, next(cycles[stmt])

    def warm_draw(self, stream: int, stmt: str, k: int) -> dict:
        """The k-th warm-up draw of a statement, counted from the END of
        its shuffled grid: the window starts at the beginning, so warming
        spends none of the window's first draws."""
        tail = self._shuffled(stream, stmt)[::-1]
        return tail[k % len(tail)]

    def warm_sends(self, stream: int, n: int) -> list:
        """``n`` rounds of the stream's statements for the warm-up."""
        order = self.order(stream)
        tails = {s: self._shuffled(stream, s)[::-1] for s in set(order)}
        return [(stmt, tails[stmt][k % len(tails[stmt])])
                for k in range(n) for stmt in order]
