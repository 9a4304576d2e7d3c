"""``batch_scan_hbm_share``: the least time the chip's memory could take
over the bytes the traced backup scanned, as a share of the device
seconds of the scan programs.  Bound: memory (one read of every scanned
byte).  The trace reduction lists the four programs with the most
seconds; where no scan program is among them there is nothing to read."""

from benchmark import kernel_bytes_batch, specs


def read(ctx: dict):
    trace, traced = ctx.get("trace"), ctx.get("traced")
    if not trace or not traced:
        return None
    names = {"program " + n for n in kernel_bytes_batch.SCAN_PROGRAMS}
    seconds = sum(secs for name, secs in trace.get("device_ops", [])
                  if name in names)
    scanned = ((traced.get("pipeline") or {}).get("bytes") or {}).get("scan")
    if not seconds or not scanned:
        return None
    peak = specs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * kernel_bytes_batch.scan_floor_seconds(scanned, peak) \
        / seconds
