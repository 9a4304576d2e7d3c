"""``tree_digest_hbm_share``: the least time the chip's memory could
take over the traced backup's tiny files, as a share of the device
seconds of the digest program.  Bound: memory (one read of every byte of
a file at or under the minimum chunk).  The trace reduction lists the
four programs with the most seconds; where the digest program is not
among them there is nothing to read."""

from benchmark import kernel_bytes_tree, specs
from benchmark.generators import source_tree

CELL = "kernel-tree.incr"


def read(ctx: dict):
    trace, traced = ctx.get("trace"), ctx.get("traced")
    if not trace or not traced:
        return None
    seconds = sum(secs for name, secs in trace.get("device_ops", [])
                  if name == "program " + kernel_bytes_tree.DIGEST_PROGRAM)
    if not seconds:
        return None
    config = specs.cell(CELL)["config"]
    tiny = kernel_bytes_tree.tiny_read_bytes_of_tree(
        traced["census"]["bytes"],
        source_tree.file_sizes(config["tree"]["params"]),
        config["cdc"]["min_size"])
    peak = specs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * kernel_bytes_tree.digest_floor_seconds(tiny, peak) \
        / seconds
