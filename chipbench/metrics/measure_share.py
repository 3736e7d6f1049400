"""Share of device busy time in ops the program charges to its ``measure``
scope (the streamed (m, E) and its ``psum``), mean over devices; the map
from op to scope is the chunk program's (``scopes.py``). Layer: site
update. Moves flips_per_ns. Silent where the program names no scope."""
import scopes

UNIT = "%"


def read(ctx):
    return scopes.share(ctx, "measure")
