"""``dump_gather_digest_hbm_share``: the least time the chip's memory
could take over the chunks the traced backup digested, as a share of the
device seconds of the gather-and-digest programs.  Bound: memory (one
read of every chunk byte; a streamed file's chunks are its bytes).  The
trace reduction lists the four programs with the most seconds; where no
gather-and-digest program is among them, or the backup streamed no file,
there is nothing to read."""

from benchmark import kernel_bytes_dump, specs


def read(ctx: dict):
    trace, traced = ctx.get("trace"), ctx.get("traced")
    if not trace or not traced:
        return None
    names = {"program " + n
             for n in kernel_bytes_dump.GATHER_DIGEST_PROGRAMS}
    seconds = sum(secs for name, secs in trace.get("device_ops", [])
                  if name in names)
    stream = (traced.get("pipeline") or {}).get("stream") or {}
    if not seconds or not stream.get("uploaded_bytes"):
        return None
    peak = specs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * kernel_bytes_dump.gather_digest_floor_seconds(
        traced["user_bytes"], peak) / seconds
