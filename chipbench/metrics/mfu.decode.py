"""Operations the forward (serving) or forward and backward (training)
pass needs for the window's work, counted from shapes by chipbench/flops.py,
over the window and the chip's bf16 peak (%)."""
from chipbench.metrics import mfu as read  # noqa: F401
