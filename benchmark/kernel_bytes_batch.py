"""Bytes the batched route's scan has to move at the least.

The scan programs read every byte of a file over the chunker's minimum
once from HBM (files at or under the minimum are one chunk each and are
never scanned); the fused program's BLAKE3 leaves can share that pass.
So the floor of the scan programs over ``n`` scanned user bytes is ``n``
bytes over the table's HBM bytes/s.  Padding of rows and buckets, the
halo and the digest's own passes are what ``batch_scan_hbm_share`` shows
as distance from 100 %.
"""

# The programs that scan on the batched route, by the names the device
# trace's ``XLA Modules`` line gives them: the shard-mapped scan + select
# + digest of a padded batch (``manifest_device._mesh_scan_digest_fn``
# jits the shard-mapped ``shard_fn``), and the segment scan of a file
# over the scan segment.  The trace reduction lists the four programs
# with the most seconds: a scan program outside them (at ``ref-1m.incr``
# the long file's two ``_scan_segment`` calls, under the fourth
# program's 0.07 s beside the manifest program's 1.6 s) adds its bytes
# and not its seconds, so the share reads that much high.
SCAN_PROGRAMS = ("jit_shard_fn", "jit__scan_segment")


def scan_read_bytes(scanned_user_bytes: int) -> int:
    return int(scanned_user_bytes)


def scan_floor_seconds(scanned_user_bytes: int,
                       hbm_bytes_per_s: float) -> float:
    return scan_read_bytes(scanned_user_bytes) / float(hbm_bytes_per_s)
