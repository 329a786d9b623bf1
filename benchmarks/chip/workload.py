"""The one traffic generator: reads a mix's parameters and makes its requests.

A mix file (``traffic/<name>.json``) gives:

``loop``
    ``{"kind": "closed", "outstanding": k}``: ``k`` callers, each sending its
    next request when its last one returns.
``groups``
    ``{"count": n, "prefix_tokens": DIST}``: each request belongs to one of
    ``n`` groups (users, tasks) whose shared prefix it starts with; ``null``
    for unshared prompts.
``body_tokens``
    DIST of the tokens each request adds after its group's prefix (a post, an
    item, or the whole document).
``labels``
    number of label tokens each request is scored over.

DIST is ``{"kind": "normal", "mean", "std", "min", "max"}``,
``{"kind": "uniform", "min", "max"}`` or ``{"kind": "lognormal", "median",
"sigma", "min", "max"}``.

Every seed gets the same work: lengths are fixed quantiles of their
distribution, and their order and the sequence of groups come from the
mix's ``pattern_seed``. Token ids (from the whole
vocabulary) and label ids come from the run's seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

import numpy as np

# streams of the run seed, so that each role draws its own tokens
_STREAMS = {"labels": 1, "prefix": 2, "body": 3, "warm": 4, "order": 5}


def quantiles(dist: Dict, n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles (i + 1/2) / n of ``dist``."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["kind"]
    if kind == "normal":
        nd = NormalDist(dist["mean"], dist["std"])
        vals = [nd.inv_cdf(q) for q in qs]
    elif kind == "uniform":
        vals = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif kind == "lognormal":
        z = NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"] * z.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", None)
    out = []
    for v in vals:
        v = max(lo, int(round(v)))
        out.append(v if hi is None else min(hi, v))
    return out


def dist_bounds(dist: Dict) -> Sequence[int]:
    return int(dist["min"]), int(dist["max"])


def ladder_bucket(n: int, ladder: Sequence[int]) -> int:
    """Smallest rung of ``ladder`` holding ``n``, doubling past the top
    (copied from the program's ``kv_policy.bucket``)."""
    for s in ladder:
        if n <= s:
            return s
    s = ladder[-1]
    while s < n:
        s *= 2
    return s


def _rng(seed: int, stream: str, *more: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), _STREAMS[stream], *more])


@dataclasses.dataclass
class Prompt:
    index: int
    group: int                  # -1 for unshared prompts
    tokens: List[int]


class Mix:
    """The requests of one traffic mix for one seed and one vocabulary."""

    def __init__(self, spec: Dict, vocab: int, seed: int):
        self.spec = spec
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.pattern_seed = int(spec.get("pattern_seed", 0))
        n_labels = int(spec["labels"])
        self.labels = [int(t) for t in _rng(seed, "labels").choice(
            self.vocab, size=n_labels, replace=False)]
        self._pattern = np.random.default_rng(self.pattern_seed)
        g = spec.get("groups")
        self.n_groups = int(g["count"]) if g else 0
        self.prefixes: List[List[int]] = []
        if g:
            lens = quantiles(g["prefix_tokens"], self.n_groups)
            lens = [lens[i] for i in _rng(
                self.pattern_seed, "order", 0).permutation(self.n_groups)]
            rng = _rng(seed, "prefix")
            self.prefixes = [rng.integers(0, self.vocab, n).tolist()
                             for n in lens]
        self._body_rng = _rng(seed, "body")
        self._warm_rng = _rng(seed, "warm")
        self._body_block = int(spec.get("body_block", 256))
        self._body_lens: List[int] = []
        self._groups: List[int] = []
        self._made = 0

    # ---- lengths and groups, in request order -------------------------------
    def _body_len(self, i: int) -> int:
        while len(self._body_lens) <= i:
            blk = len(self._body_lens) // self._body_block
            lens = quantiles(self.spec["body_tokens"], self._body_block)
            perm = _rng(self.pattern_seed, "order", 1, blk).permutation(
                self._body_block)
            self._body_lens.extend(lens[j] for j in perm)
        return self._body_lens[i]

    def _group(self, i: int) -> int:
        if not self.n_groups:
            return -1
        while len(self._groups) <= i:
            self._groups.extend(
                int(x) for x in self._pattern.integers(0, self.n_groups, 256))
        return self._groups[i]

    def _prompt(self, index: int, group: int, body_len: int,
                rng: np.random.Generator) -> Prompt:
        body = rng.integers(0, self.vocab, body_len).tolist()
        prefix = self.prefixes[group] if group >= 0 else []
        return Prompt(index, group, prefix + body)

    # ---- the window's requests ----------------------------------------------
    def next_prompt(self) -> Prompt:
        """The next request of the sequence."""
        i = self._made
        self._made += 1
        return self._prompt(i, self._group(i), self._body_len(i),
                            self._body_rng)

    # ---- set-up -------------------------------------------------------------
    def reuse_shapes(self, step: int, ladder: Sequence[int]
                     ) -> List[List[Tuple[int, int]]]:
        """For each group, ``(reused, suffix)`` token counts of one request
        per cache-hit shape first met in that group, longest reuse first.

        A cache that holds a group's prefix, or only its head because its
        tail was evicted, offers every multiple ``P`` of ``step`` up to the
        prefix's length; the rest of the prompt, ``suffix`` tokens, runs on
        the rung of ``ladder`` that holds it. A shape is the pair (``P``,
        rung); each is listed once, with the longest suffix on that rung
        that a window request of the group can have."""
        lo, hi = dist_bounds(self.spec["body_tokens"])
        seen, out = set(), []
        for prefix in self.prefixes:
            mine = []
            for p in range(len(prefix) // step * step, 0, -step):
                shortest, longest = len(prefix) - p + lo, len(prefix) - p + hi
                rung = ladder_bucket(shortest, ladder)
                while True:
                    if (p, rung) not in seen:
                        seen.add((p, rung))
                        mine.append((p, min(rung, longest)))
                    if rung >= longest:
                        break
                    rung = ladder_bucket(rung + 1, ladder)
            out.append(mine)
        return out

    def warm_prompts(self, step: int, ladder: Sequence[int]) -> List[Prompt]:
        """Set-up traffic, sent one at a time before the window: for each
        group its first request (so that a steady deployment's cache holds
        it), then one request for each of the group's cache-hit shapes
        (``reuse_shapes``): the group's first ``P`` prefix tokens and
        ``suffix`` tokens of its own, so that no window request is the
        first to run its program, whatever the cache has evicted; for
        unshared prompts, one prompt at each end of the length range.
        Tokens differ from every window request."""
        lo, hi = dist_bounds(self.spec["body_tokens"])
        out = []
        if self.n_groups:
            mid = quantiles(self.spec["body_tokens"], 1)[0]
            shapes = self.reuse_shapes(step, ladder)
            for g in range(self.n_groups):
                out.append(self._prompt(-1, g, mid, self._warm_rng))
                for p, suffix in shapes[g]:
                    tail = self._warm_rng.integers(0, self.vocab, suffix)
                    out.append(Prompt(-1, g, self.prefixes[g][:p]
                                      + tail.tolist()))
        else:
            for n in (lo, hi):
                out.append(self._prompt(-1, -1, n, self._warm_rng))
        return out

    def warm_stream(self, n: int) -> List[Prompt]:
        """``n`` requests shaped like the window's, with their own tokens, for
        the mix's warm-up phase (pack shapes of closed loops)."""
        out = []
        for i in range(n):
            out.append(self._prompt(-1, self._group(i), self._body_len(i),
                                    self._warm_rng))
        return out
