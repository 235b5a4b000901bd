"""Programs compiled or loaded inside the window; see `_common`."""
from bench.metrics._common import compiles_in_window as read  # noqa: F401
