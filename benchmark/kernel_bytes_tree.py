"""Bytes the tiny files' digest has to move at the least.

A file at or under the chunker's minimum is one chunk, never scanned:
its digest reads each of its bytes once from HBM and writes 32.  So the
floor of the digest program over files of these ``lengths`` is the sum
of those at or under ``min_size`` over the table's HBM bytes/s, the
same whatever implements the digest.  The padding of a leaf class's rows
and of its row count to a power of two, the upload, and the program's
other launches in the same backup (a bucketed file's chunk tiles, the
send stage's shard and table digests) are what ``tree_digest_hbm_share``
shows as distance from 100 %.
"""

# the digest program, by the name the device trace's ``XLA Modules``
# line gives it
DIGEST_PROGRAM = "jit_digest_padded"


def tiny_read_bytes(lengths, min_size: int) -> int:
    return sum(int(n) for n in lengths if 0 < int(n) <= int(min_size))


def tiny_read_bytes_of_tree(census_bytes: int, fixed_sizes, min_size: int
                            ) -> int:
    """The same where the lengths are known as a census and a fixed
    list: a ``source_tree``'s files over the minimum are those of its
    fixed list (a night rewrites, adds and deletes only files at or
    under it), so the rest of the census' bytes are tiny files'."""
    return int(census_bytes) - sum(int(n) for n in fixed_sizes
                                   if int(n) > int(min_size))


def digest_floor_seconds(tiny_bytes: int, hbm_bytes_per_s: float) -> float:
    return int(tiny_bytes) / float(hbm_bytes_per_s)
