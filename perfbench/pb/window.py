"""The measured window: whole steps, open and closed on step boundaries.

A step is what the cell's family says it is (one boosted tree, one data pass).
A boundary is a moment at which a known number of steps has finished on the
device and nothing of a later step has been waited for. The window opens at a
boundary after set-up, lasts at least `seconds`, and closes at the first
boundary at or after that. The rate is every step between the two boundaries
over all the time between them: stalls, host callbacks and syncs included. No
step is skipped and no reading is a median of parts.

A window of fixed work (`work`, counted in the family's `unit`, such as trees)
closes at the first boundary at which that much more work is done, whatever
the clock: it holds the same work on every run, and a faster program has a
shorter window over it. `seconds` is then only what the run was asked for.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple


@dataclasses.dataclass
class Window:
    seconds: float
    work: Optional[float] = None  # a window of fixed work: this many `unit`s
    unit: str = "seconds"  # what closes the window: the clock, or the work's unit
    t_open: Optional[float] = None
    steps_open: float = 0.0
    units_open: float = 0.0
    t_close: Optional[float] = None
    steps_close: float = 0.0
    exhausted: bool = False  # training ended by itself before the window was due

    def open(self, t: float, steps_done: float, units_done: float = 0.0) -> None:
        if self.t_open is not None:
            raise RuntimeError("window opened twice")
        self.t_open, self.steps_open = float(t), float(steps_done)
        self.units_open = float(units_done)

    @property
    def is_open(self) -> bool:
        return self.t_open is not None and self.t_close is None

    def due(self, t: float, units_done: float = 0.0) -> bool:
        """True once a boundary at time `t`, with `units_done` units of work
        done since the job began, may close the window."""
        if not self.is_open:
            return False
        if self.work is not None:
            return units_done - self.units_open >= self.work
        return t - self.t_open >= self.seconds

    def close(self, t: float, steps_done: float, exhausted: bool = False,
              units_done: float = 0.0) -> None:
        if not self.is_open:
            raise RuntimeError("window is not open")
        if not exhausted and not self.due(t, units_done):
            if self.work is not None:
                raise RuntimeError(f"window closed after {units_done - self.units_open:g} "
                                   f"{self.unit}, before {self.work:g}")
            raise RuntimeError(
                f"window closed after {t - self.t_open:.3f}s, before {self.seconds}s"
            )
        if steps_done < self.steps_open:
            raise RuntimeError("steps ran backwards")
        self.t_close, self.steps_close = float(t), float(steps_done)
        self.exhausted = exhausted

    @property
    def closed_by(self) -> str:
        """What closed the window: its unit, or the job's end."""
        return "job_end" if self.exhausted else self.unit

    @property
    def length_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def steps(self) -> float:
        return self.steps_close - self.steps_open

    @property
    def overshoot_s(self) -> Optional[float]:
        """Seconds past `seconds`; none for a window of fixed work."""
        return None if self.work is not None else self.length_s - self.seconds

    def rate(self, work_per_step: float = 1.0) -> float:
        if self.steps <= 0 or self.length_s <= 0:
            raise RuntimeError("no whole step finished inside the window")
        return self.steps * work_per_step / self.length_s


def close_on_boundaries(
    boundaries: Sequence[Tuple[float, float]], open_index: int, seconds: float
) -> Window:
    """Replay a list of (time, steps finished) boundaries: open at
    `boundaries[open_index]`, close at the first later boundary that is due,
    or at the last one (training ended by itself)."""
    w = Window(seconds)
    w.open(*boundaries[open_index])
    for t, steps in boundaries[open_index + 1:]:
        if w.due(t):
            w.close(t, steps)
            return w
    t, steps = boundaries[-1]
    w.close(t, steps, exhausted=True)
    return w
