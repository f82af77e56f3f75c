"""Idle share of the device over the traced window (%)."""
from chipbench.metrics import idle_share as read  # noqa: F401
