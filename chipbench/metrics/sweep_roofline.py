"""The sweep program's share of its HBM roofline, mean over devices.

The sweep is bound by HBM bandwidth: it does a few integer operations per
site and no matmul of any size, so its least time is the bytes it must move
(``work.sweep_bytes_per_chip``: every spin read and written once) over the
chip's HBM bandwidth (``peaks.json``). The share is that least time over
the device's busy time in the traced chunks. Layer: site update. Moves
flips_per_ns. Silent for a configuration of another ``algorithm`` than
Metropolis: a cluster sweep moves other bytes, and brings its own roofline.
"""
import work

UNIT = "%"


def read(ctx):
    if ctx.cell.config.get("algorithm", "metropolis") != "metropolis":
        return None
    tr = ctx.trace
    least_s = (work.sweep_bytes_per_chip(ctx.cell.config) * ctx.sweeps
               / ctx.peaks["hbm_bytes_per_s"])
    shares = [100.0 * least_s / (tr.busy_ns(d) / 1e9)
              for d in tr.devices() if tr.busy_ns(d) > 0]
    return sum(shares) / len(shares) if shares else None
