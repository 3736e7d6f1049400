"""Share of device busy time in ops the program charges to its ``halo``
scope (the halo lines: their rolls or ``ppermute`` and their adds into the
neighbour sums), mean over devices; the map from op to scope is the chunk
program's (``scopes.py``). Layer: halo exchange. Moves flips_per_ns.
Silent where the program names no scope."""
import scopes

UNIT = "%"


def read(ctx):
    return scopes.share(ctx, "halo")
