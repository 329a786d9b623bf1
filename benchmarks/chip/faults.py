"""Faults planted under the timed path, to show that ``correct`` catches
them: each is the program's engine with one thing broken. The CPU tests run
each on a tiny cell; ``control.py --fault <name>`` reads one at a cell's own
size on the chip."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from served import TracedEngine


class AnswerAltered(TracedEngine):
    """The score of each request is altered where it is produced."""

    def _score(self, logits, r):
        out = super()._score(logits, r)
        if out.get("scores"):
            ks = list(out["scores"])
            vs = [out["scores"][k] for k in ks]
            out["scores"] = dict(zip(ks, vs[::-1]))
        return out


class PackRowsMixed(TracedEngine):
    """Half of each packed batch gets another request's answer."""

    def _execute_packed(self, batch):
        logits = super()._execute_packed(batch)
        n = len(batch)
        order = np.arange(n)
        order[: n // 2] = np.roll(order[: n // 2 + 1], 1)[: n // 2]
        return logits[jnp.asarray(order)]


class StalePrefix(TracedEngine):
    """Cache hits attend to prefix state that was never computed (zeros)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        payloads = self.cache.match_payloads

        def stale(chain, now=0.0):
            return [tuple(jnp.zeros_like(x) for x in p)
                    for p in payloads(chain, now)]

        self.cache.match_payloads = stale


FAULTS = {f.__name__: f for f in (AnswerAltered, PackRowsMixed, StalePrefix)}
