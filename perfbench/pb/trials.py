"""Line-search trials an L-BFGS iteration, by the program's own counters.

`lbfgs.passes` adds 1 for a fit's first evaluation (one a `lbfgs.runs`) and
every trial of every line search, failed or not: the trials the harness
cannot see from outside, where a failed search leaves its loop before the
callback.
"""

from __future__ import annotations

from typing import Optional


def trials_per_iteration(run) -> Optional[float]:
    """(window's lbfgs.passes - lbfgs.runs) / lbfgs.iterations."""
    c = run.counters_window
    iterations = c.get("lbfgs.iterations")
    if not iterations or "lbfgs.passes" not in c:
        return None
    return (c["lbfgs.passes"] - c.get("lbfgs.runs", 0.0)) / iterations
