"""Percent of the traced window that is device-idle under no program span
that carries a step (`gbdt.round`, `gbdt.sync`)."""
from pb.spans import idle_unnamed_pct as read  # noqa: F401
