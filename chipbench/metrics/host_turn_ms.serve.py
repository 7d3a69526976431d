"""Host work per bucket: the server's turns less their waits, in ms."""
from chipbench.spans import host_turn_ms as read  # noqa: F401
