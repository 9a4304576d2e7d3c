"""``hbm_floor_share``: the least time the chip's memory could take over
the traced backup's user bytes, as a share of the seconds an operation
actually ran on the device.  Bound: memory (one read of every byte)."""

from benchmark import kernel_bytes, specs


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    peak = specs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    floor = kernel_bytes.hbm_floor_seconds(ctx["traced"]["user_bytes"], peak)
    return 100.0 * floor / trace["busy_s"]
