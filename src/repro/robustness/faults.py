"""Deterministic fault injection for the GSE stack (DESIGN.md §14).

The fault model is silent data corruption in the places the paper's
format actually keeps bits:

  * the packed GSE segment arrays of a :class:`~repro.sparse.csr.GSECSR`
    (head / tail1 / tail2 / colpak) and its shared-exponent ``table``;
  * the halo-exchange wire buffers of :mod:`repro.distributed.wire`
    (heads, tails, tables crossing the interconnect);
  * the memoized packed-operand entries of ``kernels/ops._cached_pack``
    (host memory in a long-lived service process).

Everything here is seeded and reproducible: ``numpy.random.default_rng``
picks (element, bit) pairs and the corruption is a plain XOR, so a CI
smoke run injects the SAME faults every time and the detection-rate gate
in ``run.py --robust`` is deterministic.

A second family of faults lives at the OPERATOR level:
:func:`make_tag_fault_operator` wraps an operator so it misbehaves only
at tags <= ``fail_tag`` (indefinite or NaN-producing) and is exact above
-- the canonical recoverable low-tag breakdown that the guard +
tag-escalation machinery (``robustness/guards.py``) must detect and
solve through.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.csr import GSECSR

__all__ = [
    "bitflip_array",
    "corrupt_gsecsr",
    "corrupt_pack_cache",
    "gsecsr_checksums",
    "verify_gsecsr",
    "make_wire_fault",
    "make_tag_fault_operator",
]

# GSECSR segments that injection may target (all fixed-width unsigned /
# int storage, so a bit-flip is well defined and silent by construction).
GSECSR_SEGMENTS = ("head", "tail1", "tail2", "colpak", "table")


def bitflip_array(arr, seed: int, nflips: int = 1):
    """Return a copy of ``arr`` with ``nflips`` seeded single-bit flips.

    Works on any fixed-width dtype: floats are reinterpreted as the
    same-width unsigned integer, flipped, and reinterpreted back --
    exactly the "cosmic ray" model (one storage bit inverted, no
    arithmetic involved).  Returns the same array type it was given
    (numpy in -> numpy out, jax in -> jax out).
    """
    was_jax = isinstance(arr, jax.Array)
    a = np.array(arr)  # host copy, always writable
    if a.size == 0 or nflips <= 0:
        return jnp.asarray(a) if was_jax else a
    width = a.dtype.itemsize * 8
    udtype = np.dtype(f"uint{width}")
    view = a.view(udtype).reshape(-1)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, view.size, size=nflips)
    bit = rng.integers(0, width, size=nflips)
    for i, b in zip(idx, bit):
        view[i] ^= udtype.type(1) << udtype.type(b)
    return jnp.asarray(a) if was_jax else a


def corrupt_gsecsr(a: GSECSR, target: str, seed: int,
                   nflips: int = 1) -> GSECSR:
    """A NEW GSECSR with seeded bit-flips in one segment.

    ``target`` is one of :data:`GSECSR_SEGMENTS`.  The original operand is
    untouched (dataclass copy) -- a test can solve with both and compare.
    Note a ``table`` flip is the high-leverage fault: one shared exponent
    scales a whole group of values (the paper's G parameter), so a single
    bit there can shift every member by powers of two.
    """
    if target not in GSECSR_SEGMENTS:
        raise ValueError(
            f"target must be one of {GSECSR_SEGMENTS}, got {target!r}")
    return dataclasses.replace(
        a, **{target: bitflip_array(getattr(a, target), seed, nflips)}
    )


def gsecsr_checksums(a: GSECSR) -> dict:
    """CRC32 per packed segment -- the reference for :func:`verify_gsecsr`."""
    out = {}
    for name in GSECSR_SEGMENTS:
        seg = np.ascontiguousarray(np.asarray(getattr(a, name)))
        out[name] = zlib.crc32(seg.tobytes())
    return out


def verify_gsecsr(a: GSECSR, ref: dict) -> list:
    """Names of segments whose CRC32 no longer matches ``ref`` (empty =
    intact)."""
    now = gsecsr_checksums(a)
    return [name for name in ref if now.get(name) != ref[name]]


def corrupt_pack_cache(a, key=None, seed: int = 0, nflips: int = 1) -> bool:
    """Silently corrupt a memoized ``_cached_pack`` entry on operator ``a``.

    Swaps bit-flipped copies of the entry's arrays into the cache while
    KEEPING the stored checksum -- modeling host-memory corruption that
    happened after the pack was built.  The next ``_cached_pack`` hit must
    detect the mismatch and repack (``PACK_STATS['corrupt']``).  Returns
    True if an entry was corrupted (False: cache empty / key absent).
    """
    cache = a.__dict__.get("_pack_cache")
    if not cache:
        return False
    if key is None:
        key = next(iter(cache))
    if key not in cache:
        return False
    entry, ck = cache[key]
    leaves, treedef = jax.tree_util.tree_flatten(entry)
    if not leaves:
        return False
    rng = np.random.default_rng(seed)
    which = int(rng.integers(0, len(leaves)))
    leaves[which] = bitflip_array(leaves[which], seed + 1, nflips)
    cache[key] = (jax.tree_util.tree_unflatten(treedef, leaves), ck)
    return True


def make_wire_fault(target: str, seed: int, nflips: int = 1) -> Callable:
    """A wire-fault hook for ``distributed.wire.set_wire_fault``.

    ``target`` names the payload to corrupt (``"head"``, ``"tail1"``,
    ``"table"``, or ``"raw"`` for the exact-wire f64 buffer).  The hook
    receives ``(name, arr)`` for each buffer about to cross the wire
    (AFTER the sender's checksum was computed) and XORs seeded bit
    positions into the matching one -- in-trace, so it works inside
    shard_map.  Flip positions are drawn on the host at hook-build time:
    deterministic per (seed, target).
    """
    def hook(name: str, arr: jnp.ndarray) -> jnp.ndarray:
        if name != target:
            return arr
        if jnp.issubdtype(arr.dtype, jnp.floating):
            width = arr.dtype.itemsize * 8
            udtype = jnp.dtype(f"uint{width}")
            bits = jax.lax.bitcast_convert_type(arr, udtype)
            flipped = _xor_flips(bits, seed, nflips)
            return jax.lax.bitcast_convert_type(flipped, arr.dtype)
        return _xor_flips(arr, seed, nflips)

    return hook


def _xor_flips(bits: jnp.ndarray, seed: int, nflips: int) -> jnp.ndarray:
    """XOR ``nflips`` seeded (element, bit) positions into an unsigned
    array, traceably (flat scatter on a static index list)."""
    width = bits.dtype.itemsize * 8
    flat = bits.reshape(-1)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, max(flat.shape[0], 1), size=nflips)
    bit = rng.integers(0, width, size=nflips)
    for i, b in zip(idx, bit):
        mask = jnp.asarray(np.array(1, flat.dtype) << np.array(b, flat.dtype))
        flat = flat.at[int(i)].set(flat[int(i)] ^ mask)
    return flat.reshape(bits.shape)


def make_tag_fault_operator(a, mode: str = "indefinite",
                            fail_tag: int = 1) -> Callable:
    """Wrap operator ``a`` so it misbehaves at tags <= ``fail_tag`` only.

    Modes (all exact at tags above ``fail_tag``):

      * ``"indefinite"`` -- negates the product: ``p.Ap`` turns negative
        on the first iteration, the textbook CG breakdown;
      * ``"nan"``        -- multiplies the product by NaN: poisons the
        residual recurrence immediately.

    ``a`` may be a GSECSR (routed through the solvers' memoized tag
    closure) or an ``apply(v, tag)`` callable.  The returned callable has
    the standard tagged-operator signature, so it drives the GENERIC
    solver paths -- a deterministic recoverable low-tag fault for the
    guard + escalation tests: detection must trip at tag <= ``fail_tag``
    and recovery must converge at ``fail_tag + 1`` or the exact tag-3
    rung.
    """
    if mode not in ("indefinite", "nan"):
        raise ValueError(f"mode must be 'indefinite' or 'nan', got {mode!r}")
    if isinstance(a, GSECSR):
        from repro.solvers.operators import make_gse_operator
        base = make_gse_operator(a)
    else:
        base = a

    def apply(v, tag):
        y = base(v, tag)
        if mode == "indefinite":
            bad = -y
        else:
            bad = y * jnp.asarray(jnp.nan, y.dtype)
        return jnp.where(jnp.asarray(tag) <= fail_tag, bad, y)

    return apply
