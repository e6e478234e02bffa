"""Independent requests: lognormal prompt and output lengths, random token ids
(nothing shared, so the prefix cache has nothing to hit), all due as one
backlog at the traffic start.

    {"kind": "independent",
     "prompt": {"median": 7059, "p90": 12985},
     "output": {"median": 208, "p90": 371},
     "arrival": {"process": "backlog", "count": 64},
     "warmup": {"completions": 3, "max_s": 240}}

Prompts are clipped to [16, max_len - max_new], outputs to at least 4. The
count is rounded up to a multiple of ``BANDS``. Lengths are ordered by
``banded_order`` with ``BANDS`` bands: prompts visit the bands in
bit-reversed order, outputs in golden-ratio order, and the seed picks the
member of each band and the token ids. ``warmup`` is read by the harness:
the window opens once ``completions`` requests have finished (at ``max_s``
at the latest).
"""
from __future__ import annotations

import numpy as np

from bench.traffic import Item, band_order, banded_order, lognormal_quantiles, round_up, tokens

BANDS = 32


class Source:
    def __init__(self, items):
        self.items = sorted(items, key=lambda it: it.due)
        self.i = 0

    def pop_due(self, now):
        out = []
        while self.i < len(self.items) and self.items[self.i].due <= now:
            it = self.items[self.i]
            it.prompt = it.prompt()  # token ids are drawn when submitted
            out.append(it)
            self.i += 1
        return out

    def next_due(self):
        return self.items[self.i].due if self.i < len(self.items) else None


def make(spec, *, seed, vocab, max_len):
    arr = spec["arrival"]
    if arr["process"] != "backlog":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = round_up(arr["count"], BANDS)
    rng = np.random.default_rng([seed, 2])
    p = banded_order(lognormal_quantiles(spec["prompt"]["median"], spec["prompt"]["p90"], n),
                     rng, BANDS, band_order("bitrev", BANDS))
    o = banded_order(lognormal_quantiles(spec["output"]["median"], spec["output"]["p90"], n),
                     rng, BANDS, band_order("golden", BANDS))
    items = []
    for i in range(n):
        max_new = max(4, int(o[i]))
        L = int(min(max(16, int(p[i])), max_len - max_new))
        items.append(Item(due=0.0, max_new=max_new,
                          prompt=lambda i=i, L=L: tokens(seed, (3, i), L, vocab)))
    return Source(items)
