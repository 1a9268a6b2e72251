"""Share of the traced window in which no operation ran on the device."""
from profile_reduce import idle_pct as read  # noqa: F401
