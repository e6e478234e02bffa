"""The comparison that decides ``correct``: served tokens against the plain
float32 reference.

After the window closes, a sample of the requests that were served tokens
(finished, or still in flight with what they had so far) is drawn from the
seed, always holding the longest (prompt plus output). For
each, the reference runs once over the prompt and the served tokens (teacher
forcing), and each served token's logit is compared with the reference's
best at its position: the gap ``max(ref) - ref[served]``. Under greedy
decoding a sound server can only pick a token whose reference logit lies
within its own rounding of the best, so the widest gap over the sample is
the number compared with the cell's limit. The control picks each position's
token from the same reference computed in fp8 instead.
"""
from __future__ import annotations

import time
from typing import List, Sequence, Tuple

import numpy as np


def sample(served: Sequence[Tuple[int, list, list]], seed: int, tokens: int,
           requests: int) -> List[Tuple[int, list, list]]:
    """(rid, prompt, output) of the longest served request, then others in
    an order drawn from the seed, until ``tokens`` served tokens or
    ``requests`` requests are in the sample."""
    if not served:
        return []
    served = sorted(served, key=lambda d: d[0])
    longest = max(served, key=lambda d: (len(d[1]) + len(d[2]), d[0]))
    rest = [d for d in served if d[0] != longest[0]]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    out = [longest]
    for i in order:
        if sum(len(d[2]) for d in out) >= tokens or len(out) >= requests:
            break
        out.append(rest[i])
    return out


def gaps(ref, cfg: dict, seed: int, seqs, control: bool = False):
    """Per served token, the reference's best logit minus the logit of the
    token taken: the served one, or with ``control`` the fp8 reference's
    first choice. Returns (gaps array, seconds)."""
    t = time.perf_counter()
    out = []
    for _rid, prompt, output in seqs:
        t1 = time.perf_counter()
        toks = list(prompt) + list(output[:-1])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(output))
        want = ref.logits_at(cfg, seed, toks, rows)
        took = np.asarray(output)
        if control:
            took = np.argmax(ref.logits_at(cfg, seed, toks, rows, fp8=True), -1)
        out.append(want.max(-1) - want[np.arange(len(rows)), took])
        print(f"[bench] reference: {len(toks)} positions, {len(rows)} compared, "
              f"widest gap {out[-1].max():.5f}, {time.perf_counter() - t1:.1f}s",
              flush=True)
    return (np.concatenate(out) if out else np.zeros((0,))), time.perf_counter() - t
