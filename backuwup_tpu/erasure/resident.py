"""One sealed packfile, resident in HBM until its stripe is coded and
audited.

``TpuBackend.encode_stripe`` hands out a :class:`ResidentStripe`: the
send stage's executor-thread half of one packfile, with the same two
members as the host composition (:class:`.stripe.Stripe`) and the same
bytes out, in two waits for the device where that one makes eight.

Dispatch one (awaited by the constructor, inside ``send.rs_encode``):
the packfile goes up once, zero-padded into the ``(k, Lb)`` matrix of
its shard-length bucket; the RS product runs on it (:mod:`.rs_tpu`'s
program); data and parity rows are stacked into one resident
``(rows, Lb)`` array and digested in place by their true length; the
parity rows and k + m digests come down.  The containers are packed on
the host: header, then a view of the packfile (data shards) or of the
downloaded parity.

Dispatch two (awaited by :meth:`ResidentStripe.challenge_tables`, inside
``send.challenge_tables``): audit windows are sampled over whole
containers, whose 48-byte headers hold the digests of dispatch one.
The device has those digests before the host does, so the constructor
launches dispatch two right behind dispatch one, before it waits for
either: the fixed 16 bytes of every header, ``(offset, length)`` rows
and nonces go up (under 10 KiB); ``nonce || container[offset:offset +
length]`` is gathered out of the resident rows for one shard's windows
at a time, the header's digest bytes taken from dispatch one's result
where it lies, and digested in the batch shape the host composition
uses for a table; one accumulator of 32 bytes a window comes down.  A
night's packfiles leave in one burst, each on a thread of its own, and
the device runs programs in the order they were launched: a stripe
whose tables were launched only after its parity came down would wait
behind every other stripe's dispatch one, and the first shard on the
wire with it (0.3 s for the first stripe of a burst of four when the RS
program took 75-97 ms, PERF.md section 6, PR 29; since PR 37 the product
is 0.08 ms a 3 MiB packfile on the v5e and a stripe's two dispatches
are ~15 ms of ``digest_padded``, PERF.md section 5).

Which programs can ever run is a function of ``(k, m)`` and the
shard-length bucket alone.  The buckets are the digest's leaf classes
(``defaults.BLAKE3_LEAF_BUCKETS``), so the heavy programs (the RS
product at ``(1, k, Lb)``, ``digest_padded`` at ``(rows, Lb)`` and at
``(windows, class)``) are shapes ``encode_shards`` and ``digest_many``
compile too; what is new is three programs that move bytes, cheap to
compile.  :func:`warm` runs every one of them once per process.
"""

from __future__ import annotations

import functools
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import defaults
from ..obs import profile as obs_profile
from ..ops.blake3_tpu import (
    CHUNK_LEN,
    _batch_bucket,
    _leaf_bucket,
    _root_cv_to_digests,
    digest_padded,
)
from ..snapshot.blob_index import ChallengeEntry
from ..wire import AUDIT_NONCE_LEN
from . import gf_cpu, rs_tpu
from .stripe import DIGEST_LEN, HEADER_LEN, shard_prefix

COUNT = defaults.AUDIT_CHALLENGES_PER_PACKFILE


def shard_bucket(shard_len: int) -> Optional[int]:
    """Bytes a shard row is padded to on the device: its leaf class.
    ``None`` above the largest class (a packfile past ``k`` x 3 MiB; the
    caller codes it with the host composition)."""
    leaves = _leaf_bucket(shard_len)
    if leaves not in defaults.BLAKE3_LEAF_BUCKETS:
        return None
    return leaves * CHUNK_LEN


def _window_span(bucket: int) -> int:
    """Longest audit window of a container in this bucket."""
    return min(defaults.AUDIT_WINDOW_BYTES, HEADER_LEN + bucket)


# --- device programs: shapes fixed by (k, m, bucket) -----------------------

@functools.partial(jax.jit, static_argnames=("rows",))
def _shard_rows(stripe: jnp.ndarray, parity: jnp.ndarray,
                *, rows: int) -> jnp.ndarray:
    """``(1, k, Lb)`` data and ``(1, m, Lb)`` parity -> the resident
    ``(rows, Lb)`` payload rows, shard index order, zero rows below."""
    both = jnp.concatenate([stripe[0], parity[0]], axis=0)
    return jnp.pad(both, ((0, rows - both.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("span", "L"))
def _window_pieces(payloads: jnp.ndarray, fixed: jnp.ndarray,
                   root: jnp.ndarray, meta: jnp.ndarray,
                   nonces: jnp.ndarray, shard: jnp.ndarray,
                   *, span: int, L: int):
    """One shard's audit pieces, ``nonce || container[off:off+len]``,
    as a zero-padded ``(COUNT, L * 1024)`` digest batch with its lengths.
    The container is the shard's header (its ``fixed`` 16 bytes, then
    its payload digest out of ``root``, little-endian words) in front of
    its resident row; bytes past a window's length are junk
    ``digest_padded`` masks."""
    def pick(arr):
        return jax.lax.dynamic_index_in_dim(arr, shard, 0, keepdims=False)

    shifts = jnp.arange(0, 32, 8, dtype=jnp.uint32)
    digest = ((pick(root)[:, None] >> shifts) & jnp.uint32(0xFF)).astype(
        jnp.uint8).reshape(-1)
    # room behind the container: dynamic_slice clamps a start it cannot
    # serve in full, without a word
    container = jnp.concatenate(
        [pick(fixed), digest, pick(payloads), jnp.zeros(span, jnp.uint8)])
    rows = pick(meta)

    def one(off, nonce):
        return jnp.concatenate(
            [nonce, jax.lax.dynamic_slice(container, (off,), (span,))])

    pieces = jax.vmap(one)(rows[:, 0], pick(nonces))
    buf = jnp.pad(pieces, ((0, 0), (0, L * CHUNK_LEN - pieces.shape[1])))
    return buf, rows[:, 1] + AUDIT_NONCE_LEN


@functools.partial(jax.jit, donate_argnames=("acc",))
def _put_digests(acc: jnp.ndarray, root: jnp.ndarray,
                 start: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(acc, root, (start, jnp.int32(0)))


@functools.lru_cache(maxsize=None)
def _parity_bits(k: int, m: int) -> np.ndarray:
    return rs_tpu.bit_matrix(gf_cpu.generator_matrix(k, m)[k:])


class ResidentStripe:
    """The device half of one packfile's stripe (module docstring):
    ``containers`` when the constructor returns, the audit tables of the
    ``missing`` shards from :meth:`challenge_tables`."""

    def __init__(self, data: bytes, k: int, m: int, bucket: int,
                 missing: Sequence[int] = (), rand=os.urandom):
        n = k + m
        flat = np.frombuffer(data, dtype=np.uint8)
        sl = gf_cpu.shard_len(flat.size, k)
        if not 0 < sl <= bucket:
            raise ValueError("shard longer than its bucket")
        rows = _batch_bucket(n)
        # the one copy the host makes of the packfile
        host = np.zeros((1, k, bucket), dtype=np.uint8)
        for i in range(k):
            part = flat[i * sl:(i + 1) * sl]
            host[0, i, :part.size] = part
        bits = _parity_bits(k, m)
        lens = np.zeros(rows, dtype=np.int32)
        lens[:n] = sl
        stripe = jnp.asarray(host)
        obs_profile.device_upload(
            host.nbytes + bits.nbytes + lens.nbytes)
        parity = rs_tpu._matmul_batched()(jnp.asarray(bits), stripe)
        payloads = _shard_rows(stripe, parity, rows=rows)
        root = digest_padded(payloads, jnp.asarray(lens),
                             L=bucket // CHUNK_LEN)
        fixed = [shard_prefix(i, k, m, flat.size) for i in range(n)]
        self._missing = [int(i) for i in missing]
        try:
            self._tables = self._launch_tables(
                payloads, root, fixed, HEADER_LEN + sl, bucket, rand)
        except Exception as e:  # auditing may fail, the stripe may not
            self._tables = e
        obs_profile.device_wait()
        parity_h = np.asarray(parity)[0]
        digests = _root_cv_to_digests(np.asarray(root))
        tail = bytes(k * sl - flat.size)  # zeros that square the last row
        self.containers: List[bytes] = []
        for i in range(k):
            part = flat[i * sl:(i + 1) * sl]
            self.containers.append(b"".join(
                (fixed[i], digests[i], part, tail[:sl - part.size])))
        for j in range(m):
            self.containers.append(b"".join(
                (fixed[k + j], digests[k + j], parity_h[j, :sl])))

    def _launch_tables(self, payloads, root, fixed: List[bytes], size: int,
                       bucket: int, rand):
        """Dispatch two, launched and not awaited: windows and nonces
        drawn as ``build_challenge_table`` draws them, shard after
        shard.  Returns what :meth:`challenge_tables` reads."""
        from ..audit.challenge import sample_windows

        if not self._missing:
            return None
        n = len(fixed)
        span = _window_span(bucket)
        meta = np.zeros((n, COUNT, 2), dtype=np.int32)
        nonces = np.zeros((n, COUNT, AUDIT_NONCE_LEN), dtype=np.uint8)
        for i in self._missing:
            meta[i] = sample_windows(size, COUNT, rand=rand)
            nonces[i] = np.frombuffer(
                b"".join(rand(AUDIT_NONCE_LEN) for _ in range(COUNT)),
                dtype=np.uint8).reshape(COUNT, AUDIT_NONCE_LEN)
        fixed_h = np.frombuffer(b"".join(fixed), dtype=np.uint8).reshape(
            n, HEADER_LEN - DIGEST_LEN)
        obs_profile.device_upload(
            fixed_h.nbytes + meta.nbytes + nonces.nbytes)
        fixed_d, meta_d, nonces_d = (jnp.asarray(a) for a in
                                     (fixed_h, meta, nonces))
        L = _leaf_bucket(AUDIT_NONCE_LEN + span)
        acc = jnp.zeros((n * COUNT, 8), dtype=jnp.uint32)
        for i in self._missing:
            buf, lens = _window_pieces(payloads, fixed_d, root, meta_d,
                                       nonces_d, np.int32(i),
                                       span=span, L=L)
            acc = _put_digests(acc, digest_padded(buf, lens, L=L),
                               np.int32(i * COUNT))
        return acc, meta, nonces

    def challenge_tables(self) -> Dict[int, list]:
        """{shard index: its ``ChallengeEntry`` table} for ``missing``,
        digests from the resident rows (dispatch two's result)."""
        if self._tables is None:
            return {}
        if isinstance(self._tables, Exception):
            raise self._tables
        acc, meta, nonces = self._tables
        obs_profile.device_wait()
        digests = _root_cv_to_digests(np.asarray(acc))
        return {i: [ChallengeEntry(offset=int(off), length=int(ln),
                                   nonce=nonces[i, w].tobytes(),
                                   digest=digests[i * COUNT + w])
                    for w, (off, ln) in enumerate(meta[i].tolist())]
                for i in self._missing}


_warm_lock = threading.Lock()
_warmed: set = set()


def warm(k: int, m: int, packfile_bytes: int) -> None:
    """Run every program of the route once, for each bucket a packfile
    of up to ``packfile_bytes`` can fall in, so that a night's tail
    packfile of any length compiles nothing.  Once per process and
    geometry; the first stripe pays it, inside whatever the caller
    counts as set-up.  Where ``encode_shards`` and ``digest_many`` ran
    at these shapes before, the heavy programs are already there."""
    top = shard_bucket(gf_cpu.shard_len(packfile_bytes, k))
    key = (k, m, top)
    if key in _warmed:
        return
    with _warm_lock:  # a burst's other stripes wait here, not compile too
        if key in _warmed:
            return
        buckets = [leaves * CHUNK_LEN
                   for leaves in defaults.BLAKE3_LEAF_BUCKETS
                   if top is None or leaves * CHUNK_LEN <= top]
        # a thread a bucket: the buckets' programs differ in shape alone
        # and XLA compiles outside the interpreter lock, so a first
        # stripe waits for the slowest bucket and not for their sum
        with ThreadPoolExecutor(len(buckets)) as pool:
            for fut in [pool.submit(
                    lambda b=b: ResidentStripe(
                        bytes(k * b), k, m, b,
                        range(k + m)).challenge_tables())
                    for b in buckets]:
                fut.result()
        _warmed.add(key)
