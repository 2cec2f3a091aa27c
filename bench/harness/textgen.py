"""Seeded inputs: texts from a record template, request sizes and arrivals.

A configuration file describes its texts as data (``"text"``): a record
template, repeated as whole records up to a byte target, and an optional
fixed-shape suffix.  A template is a list of parts; each part draws
``repeat`` (an inclusive ``[lo, hi]`` range, default ``[1, 1]``) strings
from ``one_of``, uniformly or by ``weights``::

    {"one_of": ["GET", "POST", "PUT"]}
    {"one_of": ["0", "1", "2"], "repeat": [3, 3]}

Sampling is vectorised: one numpy draw per part for a whole batch of
records, then one masked gather lays the bytes out.  Request sizes and
arrival gaps are stratified draws (the same multiset for every seed, in a
seeded order), so seeds change the content and the order of the work, not
its amount.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use (``stream``) of a run's seed."""
    return np.random.Generator(np.random.Philox(np.random.SeedSequence([seed % 2**64, stream])))


class _Part:
    def __init__(self, spec: dict):
        opts = [s.encode() for s in spec["one_of"]]
        if not opts or any(not o for o in opts):
            raise ValueError(f"a template part needs non-empty strings: {spec}")
        self.lo, self.hi = spec.get("repeat", [1, 1])
        if not 0 <= self.lo <= self.hi:
            raise ValueError(f"bad repeat range in {spec}")
        width = max(len(o) for o in opts)
        self.opt_bytes = np.zeros((len(opts), width), dtype=np.uint8)
        for i, o in enumerate(opts):
            self.opt_bytes[i, : len(o)] = np.frombuffer(o, dtype=np.uint8)
        self.opt_len = np.array([len(o) for o in opts])
        w = np.asarray(spec.get("weights", [1.0] * len(opts)), dtype=float)
        if w.shape != (len(opts),) or (w < 0).any() or w.sum() <= 0:
            raise ValueError(f"bad weights in {spec}")
        self.p = w / w.sum()

    @property
    def mean_len(self) -> float:
        return (self.lo + self.hi) / 2 * float(self.p @ self.opt_len)

    def sample(self, rng: np.random.Generator, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """(n, hi·width) bytes and the mask of the cells that are used."""
        width = self.opt_bytes.shape[1]
        if self.hi == 0:
            return np.zeros((n, 0), np.uint8), np.zeros((n, 0), bool)
        reps = rng.integers(self.lo, self.hi + 1, size=n)
        pick = rng.choice(len(self.p), size=(n, self.hi), p=self.p)
        data = self.opt_bytes[pick]                                  # (n, hi, width)
        used = (np.arange(self.hi)[None, :, None] < reps[:, None, None]) & (
            np.arange(width)[None, None, :] < self.opt_len[pick][:, :, None]
        )
        return data.reshape(n, -1), used.reshape(n, -1)


class Template:
    """Records of a text template; see the module docstring."""

    def __init__(self, spec: dict):
        self.parts = [_Part(p) for p in spec["record"]]
        self.suffix = [_Part(p) for p in spec.get("suffix", [])]
        if not self.parts:
            raise ValueError("a text template needs at least one record part")

    @staticmethod
    def _lay_out(parts, rng, n) -> Tuple[bytes, np.ndarray]:
        cols = [p.sample(rng, n) for p in parts]
        data = np.concatenate([c[0] for c in cols], axis=1)
        used = np.concatenate([c[1] for c in cols], axis=1)
        return data[used].tobytes(), used.sum(axis=1)

    def records(self, rng: np.random.Generator, n_bytes: int) -> Tuple[bytes, np.ndarray]:
        """At least ``n_bytes`` of whole records, and each record's length."""
        mean = sum(p.mean_len for p in self.parts)
        chunks, lens, total = [], [], 0
        while total < n_bytes:
            n = int((n_bytes - total) / max(mean, 1.0) * 1.05) + 16
            data, ln = self._lay_out(self.parts, rng, n)
            keep = ln > 0                        # an all-empty record adds nothing
            if not keep.all():
                starts = np.concatenate([[0], np.cumsum(ln)[:-1]])
                data = b"".join(data[s:s + l] for s, l in zip(starts[keep], ln[keep]))
                ln = ln[keep]
            chunks.append(data)
            lens.append(ln)
            total += len(data)
        return b"".join(chunks), np.concatenate(lens)

    def suffix_bytes(self, rng: np.random.Generator) -> bytes:
        return self._lay_out(self.suffix, rng, 1)[0] if self.suffix else b""

    def texts(self, rng: np.random.Generator, targets: Sequence[int]) -> List[bytes]:
        """One text per target: whole records summing to at most the target
        less the suffix (at least one record), then the suffix."""
        suffixes = [self.suffix_bytes(rng) for _ in targets]
        budgets = [max(1, int(t) - len(s)) for t, s in zip(targets, suffixes)]
        stream, lens = self.records(rng, sum(budgets))
        ends = np.cumsum(lens)
        out, pos, rec = [], 0, 0
        for budget, suffix in zip(budgets, suffixes):
            if rec >= len(lens):                 # ran short: draw more records
                more, more_lens = self.records(rng, budget)
                stream, lens = stream + more, np.concatenate([lens, more_lens])
                ends = np.cumsum(lens)
            stop = int(np.searchsorted(ends, pos + budget, side="right"))
            stop = max(stop, rec + 1)
            end = int(ends[stop - 1])
            out.append(stream[pos:end] + suffix)
            pos, rec = end, stop
        return out


class Pool:
    """Distinct long texts cut from one stream of records.

    Each ``take`` starts at a random record boundary of the stream, so texts
    of a closed loop are distinct at the cost of a slice, not of a fresh
    draw inside the measured window."""

    def __init__(self, template: Template, rng: np.random.Generator, text_bytes: int):
        self.template = template
        self.text_bytes = int(text_bytes)
        self.stream, lens = template.records(rng, 2 * self.text_bytes + 4096)
        self.ends = np.cumsum(lens)
        self.starts = self.ends - lens

    def take(self, rng: np.random.Generator) -> bytes:
        suffix = self.template.suffix_bytes(rng)
        budget = self.text_bytes - len(suffix)
        last_start = int(np.searchsorted(self.starts, len(self.stream) - budget, side="right"))
        first = int(rng.integers(0, max(1, last_start)))
        stop = int(np.searchsorted(self.ends, self.starts[first] + budget, side="right"))
        return self.stream[int(self.starts[first]):int(self.ends[max(stop, first + 1) - 1])] + suffix


def mix_sizes(n: int, sizes: Sequence[int], weights: Sequence[float],
              rng: np.random.Generator) -> np.ndarray:
    """n sizes at the n quantile midpoints of the discrete law that gives
    ``sizes[i]`` the weight ``weights[i]``, shuffled."""
    sizes = np.asarray(sizes, dtype=np.int64)
    w = np.asarray(weights, dtype=float)
    if sizes.shape != w.shape or not len(w) or (w < 0).any() or w.sum() <= 0:
        raise ValueError(f"bad size mix {sizes.tolist()} / {w.tolist()}")
    q = (np.arange(n) + 0.5) / n
    pick = np.searchsorted(np.cumsum(w) / w.sum(), q, side="right")
    return rng.permutation(sizes[np.minimum(pick, len(w) - 1)])


def poisson_arrivals(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of n requests at ``rate``/s: gaps
    at the n quantile midpoints of the exponential law, shuffled; the first
    request is due at 0."""
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
