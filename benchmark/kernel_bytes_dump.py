"""Bytes the streaming route's gather and digest have to move at the
least.

A streamed file's chunks are sliced out of the resident window into
tiles of one row a chunk and digested there; every byte of the file lies
in exactly one chunk, so the programs read each chunk byte once from HBM
at the least (a gather that feeds the digest's leaves without a tile in
between would read no more) and write 32 bytes a chunk.  So the floor
over ``n`` chunk bytes is ``n`` bytes over the table's HBM bytes/s: a
function of the chunk bytes alone, the same whatever implements the
kernel.  The tile written and read again, a row padded to its class's
length, a class's last tile padded to its height and the ``while`` of
slices that fills a tile are what ``dump_gather_digest_hbm_share`` shows
as distance from 100 %.
"""

# The programs that gather and digest a streamed window's chunks, by the
# names the device trace's ``XLA Modules`` line gives them
# (``ops/pipeline._gather_digest`` under jit, one launch a class tile).
# The trace reduction lists the four programs with the most seconds
# (ROADMAP C2): a program of this list outside them adds its bytes and
# not its seconds, so the share then reads that much high.
GATHER_DIGEST_PROGRAMS = ("jit__gather_digest",)


def gather_digest_read_bytes(chunk_bytes: int) -> int:
    return int(chunk_bytes)


def gather_digest_floor_seconds(chunk_bytes: int,
                                hbm_bytes_per_s: float) -> float:
    return gather_digest_read_bytes(chunk_bytes) / float(hbm_bytes_per_s)
