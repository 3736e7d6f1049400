"""Share of the traced window in which a collective ran on the device and
no other operation did: the halo time that compute does not hide, mean over
devices. Layer: halo exchange. Moves flips_per_ns. Silent where no
collective ran."""
import devtrace

UNIT = "%"
COLLECTIVES = ("collective-permute", "all-reduce")


def read(ctx):
    tr = ctx.trace
    exposed, seen = 0.0, False
    for d in tr.devices():
        coll = tr.op_intervals(d, lambda op: op.startswith(COLLECTIVES))
        other = tr.op_intervals(d, lambda op: not op.startswith(COLLECTIVES))
        seen = seen or bool(coll)
        exposed += devtrace.length(devtrace.subtract(coll, other))
    if not seen or tr.window_ns <= 0:
        return None
    return 100.0 * exposed / len(tr.devices()) / tr.window_ns
