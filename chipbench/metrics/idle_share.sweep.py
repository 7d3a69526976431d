"""Share of the sweep window with no op on the device."""
from chipbench.reduce import idle_share as read  # noqa: F401
