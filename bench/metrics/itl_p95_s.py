"""95th percentile of every gap between two consecutive output tokens of one
request whose later token lands inside the window."""
import numpy as np


def read(run):
    w0, w1 = run.window
    gaps = [b - a for r in run.recs.values()
            for a, b in zip(r.tokens, r.tokens[1:]) if w0 <= b < w1]
    return float(np.percentile(gaps, 95)) if gaps else None
