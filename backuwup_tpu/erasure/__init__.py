"""Erasure-coded shard placement (docs/erasure.md).

Layers:

* :mod:`.gf_cpu` — pure-numpy GF(2^8) Reed-Solomon oracle (ground truth).
* :mod:`.rs_tpu` — batched device kernel (the matrix as 8 x 8 bit
  blocks times the data's bit planes, an integer matmul mod 2 under
  ``jit``; no table indexed by data), bit-exact against the oracle.
* :mod:`.stripe` — self-describing shard containers, split/assemble/
  rebuild, and the restore-side stripe assembly tree walk.

Routing between oracle and device lives on ``ops.backend.ChunkerBackend``
(``encode_shards`` / ``decode_shards``), mirroring ``digest_many``.
"""

from .stripe import (  # noqa: F401
    SHARD_ID_LEN,
    Shard,
    StripeError,
    assemble_packfile,
    assemble_tree,
    parse_shard,
    parse_shard_id,
    rebuild_shards,
    shard_id,
    split_packfile,
)
