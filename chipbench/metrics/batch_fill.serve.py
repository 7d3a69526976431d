"""Requests over the states the server's buckets carried."""
from chipbench.reduce import batch_fill as read  # noqa: F401
