"""Paged decode kernel roofline share; see `_common`."""
from bench.metrics._common import paged_decode_roofline as read  # noqa: F401
