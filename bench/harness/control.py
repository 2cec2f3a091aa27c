"""The control: the reference in the program's place, one guarantee broken.

The configurations state no precision; they state a guarantee, that the
forest is clean (every column holds exactly the segments on some accepting
path).  The control answers every parse and request with the forward-only
forest (``Reference.packed_columns(clean=False)``), which keeps every
segment reachable from the start.  A sound comparison reads it as wrong.

``ControlParser`` keeps the real parser for the set-up the kind runners ask of it
(bucket shapes, warm-up, counters) and replaces only the answers.
"""

from __future__ import annotations

import types

from .reference import Reference, unpack_columns


class _Ticket:
    def __init__(self, make):
        self._make = make

    def result(self):
        return self._make()


class ControlParser:
    def __init__(self, real, pattern: str):
        self._real = real
        self._ref = Reference(pattern)
        self.engine, self.config, self.obs = real.engine, real.config, real.obs

    def _answer(self, text):
        cols = unpack_columns(self._ref.packed_columns(text, clean=False), self._ref.ell)
        forest = types.SimpleNamespace(columns=cols)
        return types.SimpleNamespace(forest=forest, ok=bool(cols[-1].any()))

    def parse(self, text):
        return self._answer(text)

    def submit(self, text):
        return _Ticket(lambda: self._answer(text))


def wrap(pattern: str):
    """A ``wrap_parser`` hook for ``runner.execute`` that puts the control in
    the program's place."""
    return lambda real: ControlParser(real, pattern)
