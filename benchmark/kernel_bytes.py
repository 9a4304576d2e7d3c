"""Bytes a backup's device work has to move at the least, from its sizes.

The chunk-and-fingerprint pipeline has to read every user byte once from
HBM (the gear scan and the BLAKE3 leaves can share one pass); nothing
else scales with the bytes.  So the memory floor of a backup of ``n``
user bytes is ``n`` bytes over the table's HBM bytes/s.  What the program
reads beyond that (padding, a second pass for the digest, the halo) is
what ``hbm_floor_share`` shows as distance from 100 %.
"""


def staged_read_bytes(user_bytes: int) -> int:
    return int(user_bytes)


def hbm_floor_seconds(user_bytes: int, hbm_bytes_per_s: float) -> float:
    return staged_read_bytes(user_bytes) / float(hbm_bytes_per_s)
