"""Percent of the traced window that is device-idle under no program span
that carries a step (`lbfgs.iteration`, `train.callback` and its children)."""
from pb.spans import idle_unnamed_pct as read  # noqa: F401
