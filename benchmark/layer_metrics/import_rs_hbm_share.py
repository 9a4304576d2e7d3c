"""``import_rs_hbm_share``: the least time the chip's memory could take
over the packfile bytes the traced backup coded (read once, parity
written once), as a share of the device seconds of the RS program.
Bound: memory.  The trace reduction lists the four programs with the
most seconds; where the RS program is not among them, or the program's
report has no ``send`` bytes, there is nothing to read."""

from benchmark import kernel_bytes_import, specs

CELL = "ref-1m-fresh.import"  # the one cell that lists this metric


def read(ctx: dict):
    trace, traced = ctx.get("trace"), ctx.get("traced")
    if not trace or not traced:
        return None
    names = {"program " + n for n in kernel_bytes_import.RS_PROGRAMS}
    seconds = sum(secs for name, secs in trace.get("device_ops", [])
                  if name in names)
    coded = ((traced.get("pipeline") or {}).get("send") or {}).get(
        "packfile_bytes")
    if not seconds or not coded:
        return None
    dep = specs.cell(CELL)["config"]["deployment"]
    peak = specs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * kernel_bytes_import.rs_floor_seconds(
        coded, int(dep["rs_k"]), int(dep["rs_m"]), peak) / seconds
