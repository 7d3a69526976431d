"""Device time per call in collectives (the halo exchange's permutes),
worst device: the exchange the interior work did not hide.  A cell on
one chip has no exchange and reads 0."""
from chipbench.reduce import per_call_ms


def read(r):
    return per_call_ms(r, "collective")
