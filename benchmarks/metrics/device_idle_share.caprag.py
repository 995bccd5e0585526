"""device: 1 - union of device-op intervals over the traced window."""

from benchmarks.lib.readers import idle_share as read  # noqa: F401
