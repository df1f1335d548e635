"""Record sorts: key+payload sorting with device-side payload permutation.

The stack so far sorts bare keys — a production shuffle engine sorts
*records*: each key drags an opaque payload (a row id, a serialized
tuple, a pointer-sized handle) that must land next to its key in the
output.  This module generalizes the sort to ``(key, payload)`` pairs
without teaching the SPMD kernels anything about payloads:

1. **argsort via the codec** — the keys encode through the ordinary
   order-preserving multi-word codec (``ops/keys.py``) and a uint32
   **lane-index word** is appended as the LEAST significant sort word
   (the mirror image of ``models/segmented.py``'s most-significant
   segment prefix).  One lexicographic sort of ``(*key_words, idx)``
   then yields both the sorted keys and — in the index word's output —
   the exact permutation that sorted them.  The index tiebreak makes
   the sort **stable by construction**: equal keys keep their input
   order, so the result is bit-identical to a host
   ``np.argsort(kind="stable")`` gather at any duplication level.
2. **device-side payload gather** — the payload bytes are packed into
   uint32 word columns (zero-padded to a 4-byte multiple) and permuted
   ON DEVICE by ``jnp.take(word, perm)`` inside the same fused program;
   the payload never round-trips through a host-side gather.
3. **1-word fusion** — for 1-word codecs (int32/uint32/float32, the
   common case) the ``(key, idx)`` pair fuses into ONE uint64
   ``(key << 32) | idx`` single-key sort, lowered under a scoped
   ``jax.enable_x64`` exactly like the segmented (seg,key) fusion
   (XLA:CPU's multi-operand comparator sort measured 2-4x slower than
   the single-key form); inputs and outputs stay uint32.

Verification is always-on and record-aware: the multiset fingerprint
(:func:`models.verify.fingerprint_records`) folds every key AND payload
word plus a per-record binding mix word, so a payload gathered against
the wrong key — both multisets individually intact — still trips the
check.  A failed verification re-dispatches once (transient corruption)
and then raises the typed :class:`SortIntegrityError`.

Payload transfers ride the PR 2 staging contract: every host→device
move goes through ``checked_device_put`` (the dtype-preservation
guard), and the external-sort path (``store/external.py``) stages
payload chunks through the same spill framing as the keys.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Callable

import numpy as np

from mpitest_tpu.models import verify as vfy
from mpitest_tpu.models.ingest import checked_device_put
from mpitest_tpu.models.segmented import lex_sorted_host
from mpitest_tpu.models.supervisor import SortIntegrityError, verify_enabled
from mpitest_tpu.ops.keys import KeyCodec, codec_for

#: Hard bound on records per sort: the lane-index word is uint32 and
#: the (key<<32|idx) fusion gives the index the low 32 bits.
MAX_RECORDS = 1 << 31

#: Payload bytes pack into this many-byte words (uint32 columns).
_WORD_BYTES = 4


def payload_width_words(width: int) -> int:
    """uint32 words per record for a ``width``-byte payload."""
    return (int(width) + _WORD_BYTES - 1) // _WORD_BYTES


def as_payload_matrix(payload: Any, n: int) -> np.ndarray:
    """Canonicalize a payload argument to a ``(n, width)`` uint8 matrix.

    Accepts ``bytes`` / 1-D uint8 of ``n * width`` bytes (width
    inferred), a ``(n, width)`` uint8 matrix, or any fixed-itemsize
    array of ``n`` elements (viewed as its raw little-endian bytes —
    a uint64 row-id array is a valid 8-byte payload as-is)."""
    if isinstance(payload, (bytes, bytearray, memoryview)):
        payload = np.frombuffer(bytes(payload), np.uint8)
    arr = np.asarray(payload)
    if arr.dtype != np.uint8:
        if arr.ndim != 1 or arr.shape[0] != n:
            raise ValueError(
                f"payload array must be 1-D with one element per record "
                f"(got shape {arr.shape} for {n} records)")
        arr = np.ascontiguousarray(arr).view(np.uint8).reshape(n, -1)
    if arr.ndim == 1:
        if n == 0:
            return arr.reshape(0, 0)
        if arr.size % n:
            raise ValueError(
                f"payload of {arr.size} bytes is not a multiple of the "
                f"record count {n}")
        arr = arr.reshape(n, arr.size // n)
    if arr.ndim != 2 or arr.shape[0] != n:
        raise ValueError(
            f"payload must be (n, width) bytes; got shape {arr.shape} "
            f"for {n} records")
    return np.ascontiguousarray(arr)


def payload_to_words(payload: np.ndarray) -> tuple[np.ndarray, ...]:
    """``(n, width)`` uint8 payload -> per-record uint32 word columns
    (little-endian, zero-padded to a word multiple).  Zero columns for
    a zero-width payload."""
    n, width = payload.shape
    pw = payload_width_words(width)
    if pw == 0:
        return ()
    padded = payload
    if width % _WORD_BYTES:
        padded = np.zeros((n, pw * _WORD_BYTES), np.uint8)
        padded[:, :width] = payload
    cols = padded.reshape(n, pw, _WORD_BYTES).view(np.uint32)[..., 0]
    return tuple(np.ascontiguousarray(cols[:, j]) for j in range(pw))


def words_to_payload(words: tuple[np.ndarray, ...], n: int,
                     width: int) -> np.ndarray:
    """Inverse of :func:`payload_to_words`: word columns -> ``(n,
    width)`` uint8 payload (the zero pad is dropped)."""
    pw = payload_width_words(width)
    if pw == 0:
        return np.zeros((n, 0), np.uint8)
    mat = np.empty((n, pw), np.uint32)
    for j, w in enumerate(words):
        mat[:, j] = w
    return mat.view(np.uint8).reshape(n, pw * _WORD_BYTES)[:, :width].copy()


@lru_cache(maxsize=32)
def _compile_record_sort(n_key_words: int, n_payload_words: int,
                         n: int) -> Callable[..., Any]:
    """AOT-compile the fused record program for one shape: sort
    ``(*key_words, idx)`` lexicographically (idx = appended uint32 lane
    index, the stability tiebreak AND the permutation), then gather
    every payload word by the sorted index — one dispatch, no host
    round-trip between argsort and gather.

    ``n`` is always a power-of-two shape bucket
    (:func:`models.segmented.bucket_for` — callers pad, see
    :func:`_dispatch`), so a serve mix of assorted record sizes reuses
    a handful of executables instead of paying an XLA compile per
    distinct request size on the dispatch thread.

    1-word keys fuse ``(key << 32) | idx`` into a single uint64 sort
    key, LOWERED under a scoped ``enable_x64`` (the segmented.py
    pattern — u32 in/out, callers never see the flag)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    specs = tuple(jax.ShapeDtypeStruct((n,), jnp.uint32)
                  for _ in range(n_key_words + n_payload_words))

    def gather(perm: Any, payload: tuple[Any, ...]) -> tuple[Any, ...]:
        return tuple(jnp.take(w, perm) for w in payload)

    if n_key_words == 1:
        def f1(*arrs: Any) -> Any:
            key, payload = arrs[0], arrs[1:]
            idx = lax.iota(jnp.uint32, n)
            u = ((key.astype(jnp.uint64) << np.uint64(32))
                 | idx.astype(jnp.uint64))
            s = lax.sort([u], num_keys=1, is_stable=False)[0]
            perm = s.astype(jnp.uint32)
            return ((s >> np.uint64(32)).astype(jnp.uint32),), \
                gather(perm, payload), perm

        with jax.enable_x64(True):
            return jax.jit(f1).lower(*specs).compile()

    def f(*arrs: Any) -> Any:
        kw, payload = arrs[:n_key_words], arrs[n_key_words:]
        idx = lax.iota(jnp.uint32, n)
        out = lax.sort(list(kw) + [idx], num_keys=n_key_words + 1,
                       is_stable=False)
        perm = out[-1]
        return tuple(out[:n_key_words]), gather(perm, payload), perm

    return jax.jit(f).lower(*specs).compile()


def _dispatch(codec: KeyCodec, key_words: tuple[np.ndarray, ...],
              payload_words: tuple[np.ndarray, ...], n: int,
              device: Any) -> tuple[tuple[np.ndarray, ...],
                                    tuple[np.ndarray, ...]]:
    """One staged record dispatch: pad to the power-of-two shape
    bucket, device_put (guarded), run the fused program, fetch and
    slice the sorted words back on the host.

    Pad lanes carry all-ones key words (the lexicographic maximum) and
    lane indices >= n, so they sort strictly after every real record —
    a real all-ones key still wins its tie by index — and the first
    ``n`` output lanes are exactly the sorted real records.  Bucketing
    (the ``segmented.bucket_for`` rule) is what keeps the executable
    zoo bounded under a serve mix of assorted record sizes."""
    from mpitest_tpu.models.segmented import bucket_for

    bucket = bucket_for(n)
    if bucket > n:
        pad = bucket - n
        key_words = tuple(
            np.concatenate([w, np.full(pad, 0xFFFFFFFF, np.uint32)])
            for w in key_words)
        payload_words = tuple(
            np.concatenate([w, np.zeros(pad, np.uint32)])
            for w in payload_words)
    exe = _compile_record_sort(codec.n_words, len(payload_words),
                               bucket)
    dev_args = tuple(checked_device_put(w, device)
                     for w in key_words + payload_words)
    out_kw, out_pw, _perm = exe(*dev_args)
    return (tuple(np.asarray(w)[:n] for w in out_kw),
            tuple(np.asarray(w)[:n] for w in out_pw))


def sort_records(keys: np.ndarray, payload: Any,
                 mesh: Any = None, tracer: Any = None,
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Sort ``keys`` with their per-record ``payload`` permuted along
    (stable by key; see the module docstring).  Returns ``(sorted_keys,
    sorted_payload)`` where the payload comes back as a ``(n, width)``
    uint8 matrix.

    Always verified: the output must be lexicographically sorted AND
    reproduce the record fingerprint (key+payload+binding mix) folded
    from the input — one transient-corruption retry, then a typed
    :class:`SortIntegrityError`."""
    keys = np.asarray(keys).reshape(-1)
    n = int(keys.size)
    if n >= MAX_RECORDS:
        raise ValueError(f"record sort supports < 2^31 records, got {n}")
    dtype = np.dtype(keys.dtype)
    codec = codec_for(dtype)
    pay = as_payload_matrix(payload, n)
    width = int(pay.shape[1])
    if n == 0:
        return np.empty(0, dtype), pay.reshape(0, width)

    if mesh is None:
        from mpitest_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(1)
    device = mesh.devices.flat[0]

    verify_on = verify_enabled()
    key_words = codec.encode(keys)
    payload_words = payload_to_words(pay)
    fp_in = (vfy.fingerprint_records(key_words, payload_words)
             if verify_on else None)

    spans = tracer.spans if tracer is not None else None
    for attempt in range(2 if verify_on else 1):
        out_kw, out_pw = _dispatch(codec, key_words, payload_words, n,
                                   device)
        if not verify_on:
            break
        sorted_ok = lex_sorted_host(out_kw)
        fp_ok = vfy.fingerprint_records(out_kw, out_pw) == fp_in
        ok = sorted_ok and fp_ok
        if spans is not None:
            spans.event("verify", ok=bool(ok),
                        sorted_ok=bool(sorted_ok),
                        fp_ok=bool(fp_ok), n=n)
        if tracer is not None:
            tracer.count("verify_runs", 1)
        if ok:
            break
        if tracer is not None:
            tracer.count("verify_failures", 1)
        if attempt:
            raise SortIntegrityError(
                "record sort failed fingerprint verification twice "
                "(keys, payload, or their pairing corrupted)")
    return codec.decode(out_kw), words_to_payload(out_pw, n, width)
