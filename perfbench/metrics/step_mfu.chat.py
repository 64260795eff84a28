"""Model operations of the real tokens of the traced packed steps over
their device time, as a share of the chip's bf16 peak (chat: moves itl_p95_ms)."""

from perfbench.readers import step_mfu as read  # noqa: F401
