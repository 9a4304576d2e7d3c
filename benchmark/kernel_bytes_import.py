"""Bytes the RS encode of a backup's packfiles has to move at the least.

The RS program reads every packfile byte once from HBM (the ``k`` data
shards) and writes its parity once (``m`` shards of the same length), so
the floor over ``n`` packfile bytes is ``n x (k + m) / k`` bytes over the
table's HBM bytes/s.  A function of the packfile bytes and the geometry
alone: whatever implements the kernel is read against the same work.
Padding of a packfile to its shard-length bucket, the staging copies and
the digests behind the product are what ``import_rs_hbm_share`` shows as
distance from 100 %.
"""

# The program that codes a stripe, by the name the device trace's ``XLA
# Modules`` line gives it (``erasure/rs_tpu.rs_gf_matmul`` under jit).
RS_PROGRAMS = ("jit_rs_gf_matmul",)


def rs_bytes(packfile_bytes: int, k: int, m: int) -> float:
    return float(packfile_bytes) * (k + m) / k


def rs_floor_seconds(packfile_bytes: int, k: int, m: int,
                     hbm_bytes_per_s: float) -> float:
    return rs_bytes(packfile_bytes, k, m) / float(hbm_bytes_per_s)
