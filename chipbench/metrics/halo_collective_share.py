"""Share of the traced window in which a collective ran on the device
(opcodes ``collective-permute*`` and ``all-reduce*``, their ``-start`` and
``-done`` halves included), mean over devices. Layer: halo exchange. Moves
flips_per_ns. Silent where no collective ran."""
UNIT = "%"
COLLECTIVES = ("collective-permute", "all-reduce")


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVES)


def read(ctx):
    tr = ctx.trace
    spans = {d: tr.op_intervals(d, is_collective) for d in tr.devices()}
    if not any(spans.values()) or tr.window_ns <= 0:
        return None
    total = sum(sum(e - s for s, e in v) for v in spans.values())
    return 100.0 * total / len(spans) / tr.window_ns
