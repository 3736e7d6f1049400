"""Share of the traced window in which no operation ran on the device,
mean over the cell's devices. Layer: device. Moves flips_per_ns."""
UNIT = "%"


def read(ctx):
    tr = ctx.trace
    if not tr.devices() or tr.window_ns <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_ns() / tr.window_ns)
