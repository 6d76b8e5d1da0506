"""Kernel launch plans and the single resolution dispatcher (PR 7).

Every SpMV/SpMM kernel entry point used to hardcode ``blocks=(8, 128)``.
They now resolve their launch configuration through :func:`resolve`, with
a fixed precedence:

  1. explicit ``blocks=`` argument        (today's call sites, unchanged)
  2. explicit ``plan=KernelPlan(...)``    (caller-owned plan)
  3. tuned cache entry                    (``perf.tunecache``, keyed by
                                           ``(device | shape-class | tag |
                                           layout | nrhs)``)
  4. :data:`DEFAULT_PLAN`                 (bit-identical to pre-PR-7
                                           behavior -- blocks (8, 128),
                                           lane 128, SELL C=8 / full-sort
                                           sigma / pow2 width buckets)

so with an empty tune cache and no explicit arguments every kernel runs
exactly as before (asserted in tests/test_perf.py).

The shape class buckets operators by power-of-two row count and mean
row length -- coarse on purpose: a tuned winner should transfer across
same-family matrices, and the class must be derivable identically from a
``GSECSR``/``CSR`` (rowptr) and from an already-packed ``GSESellC``
(shape + nnz), so dispatch-time lookups hit the keys the autotuner stored.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.perf import tunecache

__all__ = ["KernelPlan", "DEFAULT_PLAN", "DEFAULT_BLOCKS", "resolve",
           "shape_class", "plan_key", "tag_token", "device_token"]

DEFAULT_BLOCKS = (8, 128)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """One kernel launch configuration (DESIGN.md section 15).

    ``blocks``      -- (BM, BL) Pallas grid tile: BM rows x BL lanes;
    ``lane``        -- pack lane alignment (ELL width / SELL slice widths
                       round up to multiples of this);
    ``sell_c``      -- SELL slice height C (multiple of 8, and BM must
                       divide it);
    ``sell_sigma``  -- SELL sort-window sigma (None = full sort);
    ``sell_bucket`` -- SELL width-bucket granularity: "pow2" bins slice
                       widths into power-of-two lane multiples (bounded
                       kernel-call count), "exact" keeps each distinct
                       lane-aligned width (zero bucket padding, more
                       calls);
    ``source``      -- provenance ("default" / "explicit" / "tuned"),
                       excluded from equality so a tuned plan that picks
                       the default configuration compares equal to it.
    """

    blocks: tuple = DEFAULT_BLOCKS
    lane: int = 128
    sell_c: int = 8
    sell_sigma: int | None = None
    sell_bucket: str = "pow2"
    source: str = dataclasses.field(default="default", compare=False)

    def to_dict(self) -> dict:
        return {
            "blocks": list(self.blocks),
            "lane": self.lane,
            "sell_c": self.sell_c,
            "sell_sigma": self.sell_sigma,
            "sell_bucket": self.sell_bucket,
        }

    @classmethod
    def from_dict(cls, d: dict, source: str = "tuned") -> "KernelPlan":
        return cls(
            blocks=tuple(d.get("blocks", DEFAULT_BLOCKS)),
            lane=int(d.get("lane", 128)),
            sell_c=int(d.get("sell_c", 8)),
            sell_sigma=(None if d.get("sell_sigma") is None
                        else int(d["sell_sigma"])),
            sell_bucket=str(d.get("sell_bucket", "pow2")),
            source=source,
        )

    def compatible_with_sell(self, sell) -> bool:
        """Can ``blocks`` drive an ALREADY-packed ``GSESellC``?  (The pack
        fixes C and the bucket widths; a tuned plan recorded for a
        different pack must fall back instead of raising.)"""
        bm, bl = self.blocks
        return (sell.c % bm == 0
                and all(w % bl == 0 for w in sell.widths))


DEFAULT_PLAN = KernelPlan()


def _p2(x: float) -> int:
    n = 1
    while n < x:
        n *= 2
    return n


def shape_class(obj) -> str:
    """Coarse matrix class: pow2-bucketed rows x pow2-bucketed mean row
    length.  Works for any container exposing ``shape`` and ``nnz``
    (``CSR``, ``GSECSR``, ``GSESellC``, ``ELLLayout`` ducks in too)."""
    rows = int(obj.shape[0])
    nnz = int(obj.nnz)
    mean_row = max(1, -(-nnz // max(rows, 1)))
    return f"m{_p2(rows)}r{_p2(mean_row)}"


def tag_token(tag) -> str:
    """Cache-key token of a precision axis value.

    Scalar tags keep the pre-PR-10 ``tag{t}`` token (existing tune-cache
    entries stay resolvable); a per-group :class:`~repro.core.tagmap.
    TagMap` keys under its CRC32 -- ``map{crc:08x}`` -- so a promoted map
    can never resolve a plan tuned for a different (stale) map.
    """
    crc = getattr(tag, "crc32", None)
    if crc is not None:
        return f"map{crc:08x}"
    return f"tag{tag}"


def device_token() -> str:
    """Cache-key token of the device a plan is tuned on:
    ``platform:device_kind`` of ``jax.devices()[0]``.  A plan swept under
    the CPU interpreter must never resolve on a TPU (or on another TPU
    generation)."""
    dev = jax.devices()[0]
    return f"{dev.platform}:{dev.device_kind}"


def plan_key(shape_cls: str, tag, layout: str, nrhs: int = 1) -> str:
    """Tune-cache key: ``device | shape-class | tag-token | layout |
    nrhs``."""
    return (f"{device_token()}|{shape_cls}|{tag_token(tag)}|{layout}"
            f"|nrhs{int(nrhs)}")


def resolve(source=None, *, tag=None, layout: str | None = None,
            nrhs: int = 1, plan: KernelPlan | None = None,
            blocks=None) -> KernelPlan:
    """The single launch-plan dispatcher (precedence documented above).

    ``source`` is an optional operand container (``GSECSR``/``GSESellC``/
    ...) enabling the tuned-cache lookup; without it (or without ``tag``/
    ``layout``) resolution goes straight to the default plan, which keeps
    bare array-level entry points (``gse_spmv_ell`` on raw segment
    tuples) bit-identical to their pre-PR-7 behavior.
    """
    if blocks is not None:
        base = plan if plan is not None else DEFAULT_PLAN
        return dataclasses.replace(base, blocks=tuple(blocks),
                                   source="explicit")
    if plan is not None:
        if plan.source == "default":
            plan = dataclasses.replace(plan, source="explicit")
        return plan
    if source is not None and tag is not None and layout is not None:
        payload = tunecache.lookup(plan_key(shape_class(source), tag,
                                            layout, nrhs))
        if payload is not None:
            return KernelPlan.from_dict(payload.get("plan", payload),
                                        source="tuned")
    return DEFAULT_PLAN
