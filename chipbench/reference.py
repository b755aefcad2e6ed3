"""Plain reference for what a committed save must hold.

Independent of the program under test: nothing here imports `repro`.
It knows only the formats a user of the store can see: how a state tree
is walked into leaf paths, the flat-range chunk grid that names chunk
keys (`<leaf path>#[<index>]`), the digest table a manifest carries, and
the published digest function that the fingerprint kernel must compute.

* `flatten` walks a state tree into {path: leaf}.
* `chunk_grid` is the store's chunk grid (elements per chunk, chunks).
* `chunk_checksums` hashes every chunk of a device array on the device,
  two independent 32-bit lanes per chunk, so a saved snapshot can be
  recorded in the window without copying it off the chip.  Any change
  of a single element changes the first lane (odd multipliers are
  invertible mod 2**32).
* `fingerprint_chunks_np` is the numpy twin of the chunk digest (the
  spec in the program's kernel module, restated here), zero tolerance.
* `unpack_digest_table` reads a manifest's `chunks` table.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

# -- state trees ---------------------------------------------------------


def _is_array(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype") and hasattr(x, "nbytes")


def flatten(tree: Any) -> Dict[str, Any]:
    """{path: leaf} in the store's walk order: dict keys as `str(key)`,
    list and tuple items by index, everything else a leaf."""
    out: Dict[str, Any] = {}

    def walk(prefix: Tuple[str, ...], x: Any) -> None:
        if isinstance(x, dict):
            for k in x:
                walk(prefix + (str(k),), x[k])
        elif isinstance(x, (list, tuple)) and not _is_array(x):
            for i, v in enumerate(x):
                walk(prefix + (str(i),), v)
        else:
            out["/".join(prefix)] = x

    walk((), tree)
    return out


def array_leaves(tree: Any) -> Dict[str, Any]:
    return {k: v for k, v in flatten(tree).items() if _is_array(v)}


# -- chunk grid ----------------------------------------------------------


def chunk_grid(shape: Tuple[int, ...], dtype: Any,
               chunk_bytes: int) -> Tuple[int, int]:
    """(elements per chunk, number of chunks) over the C-order flattened
    array; chunk boundaries are 4-byte aligned."""
    total = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if total == 0:
        return 1, 1
    itemsize = np.dtype(dtype).itemsize
    elems = max(1, chunk_bytes // itemsize)
    if elems >= total:
        return total, 1
    if (elems * itemsize) % 4:
        mult = 2 if (itemsize * 2) % 4 == 0 else 4
        elems = max(mult, (elems // mult) * mult)
        if elems >= total:
            return total, 1
    return elems, -(-total // elems)


# -- on-device chunk checksums -------------------------------------------

_CK_MULT = (0x9E3779B1, 0x85EBCA77)
_CK_SALT = (0x7F4A7C15, 0x165667B1)


def _mix32(z):
    import jax.numpy as jnp
    z = z ^ (z >> jnp.uint32(16))
    z = z * jnp.uint32(0x7FEB352D)
    z = z ^ (z >> jnp.uint32(15))
    z = z * jnp.uint32(0x846CA68B)
    return z ^ (z >> jnp.uint32(16))


def _words(v):
    """(n, elems) array of a 1-, 2- or 4-byte dtype -> (n, elems) uint32,
    one word per element (JAX holds no 8-byte arrays without x64)."""
    import jax
    import jax.numpy as jnp
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.uint8)
    isz = np.dtype(v.dtype).itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(v, jnp.uint32)
    narrow = {2: jnp.uint16, 1: jnp.uint8}[isz]
    return jax.lax.bitcast_convert_type(v, narrow).astype(jnp.uint32)


def chunk_checksums(x, chunk_bytes: int):
    """int32 (n_chunks, 2) checksums of one device array's chunks."""
    import jax
    import jax.numpy as jnp
    elems, n_chunks = chunk_grid(tuple(x.shape), x.dtype, chunk_bytes)
    flat = x.reshape(-1)
    pad = n_chunks * elems - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    w = _words(flat.reshape(n_chunks, elems))
    i = jnp.arange(w.shape[1], dtype=jnp.uint32)[None, :]
    lanes = []
    for mult, salt in zip(_CK_MULT, _CK_SALT):
        weight = _mix32(i * jnp.uint32(mult) + jnp.uint32(salt)) | jnp.uint32(1)
        v = w if not lanes else (w << jnp.uint32(13)) | (w >> jnp.uint32(19))
        prod = jax.lax.bitcast_convert_type(v * weight, jnp.int32)
        lanes.append(jnp.sum(prod, axis=1, dtype=jnp.int32))
    return jnp.stack(lanes, axis=1)


def tree_chunk_checksums(leaves: Dict[str, Any], chunk_bytes: int):
    """{path: (n_chunks, 2)} for a dict of device arrays, one jitted call."""
    return _tree_checksum_fn(chunk_bytes)(leaves)


_TREE_FNS: Dict[int, Any] = {}


def _tree_checksum_fn(chunk_bytes: int):
    import jax
    fn = _TREE_FNS.get(chunk_bytes)
    if fn is None:
        def chipbench_checksums(leaves):
            return {k: chunk_checksums(v, chunk_bytes)
                    for k, v in leaves.items()}
        fn = _TREE_FNS[chunk_bytes] = jax.jit(chipbench_checksums)
    return fn


# -- digests -------------------------------------------------------------

_LANE_PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)
_PHI32 = 0x9E3779B9
_STREAM_SALT = 0x632BE59B


def _mix32_np(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.uint32)
    z = z ^ (z >> np.uint32(16))
    z = (z * np.uint32(0x7FEB352D)).astype(np.uint32)
    z = z ^ (z >> np.uint32(15))
    z = (z * np.uint32(0x846CA68B)).astype(np.uint32)
    return z ^ (z >> np.uint32(16))


def fingerprint_words_np(words: np.ndarray, lengths: np.ndarray,
                         seed: int = 0) -> np.ndarray:
    """Digest spec, uint32 mod 2**32: weight_j(i) = mix32(i*A_j + seed +
    j*SALT); digest[c, j] = sum_i words[c, i] * weight_j(i) +
    mix32(length[c] ^ (j+1)*PHI) + seed."""
    words = np.asarray(words, np.uint32)
    i = np.arange(words.shape[1], dtype=np.uint32)
    out = np.zeros((words.shape[0], 4), np.uint32)
    with np.errstate(over="ignore"):
        for j, prime in enumerate(_LANE_PRIMES):
            w = _mix32_np((i * np.uint32(prime)).astype(np.uint32)
                          + np.uint32(seed)
                          + np.uint32((j * _STREAM_SALT) & 0xFFFFFFFF))
            s = (words * w[None, :]).astype(np.uint32).sum(
                axis=1, dtype=np.uint32)
            fold = _mix32_np(np.asarray(lengths, np.uint32)
                             ^ np.uint32(((j + 1) * _PHI32) & 0xFFFFFFFF))
            out[:, j] = (s + fold + np.uint32(seed)).astype(np.uint32)
    return out


def fingerprint_chunks_np(arr: np.ndarray, chunk_bytes: int,
                          seed: int = 0) -> np.ndarray:
    """uint32 (n_chunks, 4) digests of a host array on the chunk grid:
    each chunk's bytes as little-endian words, zero-padded."""
    a = np.ascontiguousarray(arr)
    if a.dtype == np.bool_:
        a = a.astype(np.uint8)
    elems, n_chunks = chunk_grid(a.shape, a.dtype, chunk_bytes)
    raw = a.reshape(-1).view(np.uint8)
    step = elems * a.dtype.itemsize
    width = -(-step // 4) * 4
    buf = np.zeros(n_chunks * width, np.uint8)
    lengths = np.empty(n_chunks, np.uint32)
    for ci in range(n_chunks):
        part = raw[ci * step:(ci + 1) * step]
        buf[ci * width:ci * width + part.size] = part
        lengths[ci] = part.size
    words = buf.view("<u4").reshape(n_chunks, width // 4)
    return fingerprint_words_np(words, lengths, seed=seed)


def unpack_digest_table(packed: Dict[str, bytes]) -> Dict[str, bytes]:
    """Manifest `chunks` table ({leaf path: concatenated 16-byte digests
    in chunk order}) -> {chunk key: digest}."""
    out: Dict[str, bytes] = {}
    for path, blob in packed.items():
        for ci in range(len(blob) // 16):
            out[f"{path}#[{ci}]"] = bytes(blob[16 * ci:16 * (ci + 1)])
    return out
