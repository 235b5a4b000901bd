"""Whole-step share of the bf16 peak; see `_common.step_mfu`."""
from bench.metrics._common import step_mfu as read  # noqa: F401
