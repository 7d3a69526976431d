"""Share of the serve window the device idles in the turn's host work."""
from chipbench.spans import idle_in_turn as read  # noqa: F401
