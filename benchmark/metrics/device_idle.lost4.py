"""Share of the traced rebuild.lost4 window in which no op runs on the
device."""

from trace_reduce import idle_share as read  # noqa: F401
