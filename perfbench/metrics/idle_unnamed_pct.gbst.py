"""Percent of the traced window of soft trees that is device-idle under no
program span that carries a step (`gbst.tree`, `lbfgs.iteration`)."""
from pb.spans import idle_unnamed_pct as read  # noqa: F401
