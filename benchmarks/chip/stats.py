"""Window statistics shared by the metric readers: which tokens, gaps and
requests fall in the measured window, and their percentiles."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (numpy's linear rule), or None when there
    is nothing to read."""
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def window_steps(run) -> list:
    """Engine steps that began inside the window."""
    return [s for s in run.steps if run.t0 <= s.t0 < run.t_end]


def window_tokens(run) -> int:
    """Tokens stamped inside the window, from every request."""
    return sum(1 for tr in run.tracks for t in tr.stamps
               if run.t0 <= t <= run.t_end)


def window_gaps(run) -> List[float]:
    """Seconds between consecutive tokens of a request, both inside the
    window."""
    out = []
    for tr in run.tracks:
        st = [t for t in tr.stamps if run.t0 <= t <= run.t_end]
        out += list(np.diff(st)) if len(st) > 1 else []
    return out
