"""Stepped mixed-precision controller (paper Section III.D, Eq. 3-6).

Pure-functional residual monitor usable inside ``jax.lax.while_loop``:
state is a fixed-size ring buffer of recent residuals plus counters.

Metrics over the trailing window of ``t`` residuals (paper Eq. 3-6):

  RSD     relative standard deviation of the window
  nDec    number of strict decreases resid[i] > resid[i+1]
  relDec  (resid[j-t] - resid[j-1]) / resid[j-t]

Switch-up conditions (any one fires => precision tag += 1):

  C1:  RSD > rsd_limit  and  nDec < ndec_limit     (stall with oscillation)
  C2:  nDec >= ndec_limit and relDec < reldec_limit (decreasing, too slowly)
  C3:  nDec == 0                                    (no decrease at all)

NOTE on paper fidelity: the paper's Condition-2 text is elliptical
("nDec >= t/2 && relDec_limit"); its parameter list names an explicit
``nDec_limit`` (80 for GMRES with t=300; 130 for CG with t=250).  We
therefore use a configurable ``ndec_limit`` defaulting to ``t // 2`` and
read C2 as ``relDec < reldec_limit``, which matches the prose ("the rate of
residual decrease ... was slower").
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tagmap import GROUP_SIZE, TagMap

__all__ = ["MonitorParams", "MonitorState", "init", "record", "metrics",
           "update_tag", "group_sensitivity", "decode_error_scores",
           "map_floor_contrib", "plan_tagmap", "promote_groups",
           "stalled"]


@dataclasses.dataclass(frozen=True)
class MonitorParams:
    """Static controller parameters (paper Section IV.D.1)."""

    t: int = 250              # trailing window length
    l: int = 3000             # iterations before first possible switch
    m: int = 500              # check cadence
    rsd_limit: float = 0.50
    reldec_limit: float = 0.45
    ndec_limit: int | None = None  # default: t // 2
    max_tag: int = 3

    @property
    def ndec(self) -> int:
        return self.t // 2 if self.ndec_limit is None else self.ndec_limit

    @classmethod
    def for_gmres(cls) -> "MonitorParams":
        return cls(t=300, l=9000, m=1500, rsd_limit=0.03, reldec_limit=0.08,
                   ndec_limit=80)

    @classmethod
    def for_cg(cls) -> "MonitorParams":
        return cls(t=250, l=3000, m=500, rsd_limit=0.50, reldec_limit=0.45,
                   ndec_limit=130)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MonitorState:
    hist: jnp.ndarray   # (t,) f64/f32 ring buffer of residuals
    count: jnp.ndarray  # () int32 residuals recorded so far
    tag: jnp.ndarray    # () int32 current precision tag (1..3)

    def tree_flatten(self):
        return (self.hist, self.count, self.tag), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


def init(params: MonitorParams, dtype=jnp.float64, tag: int = 1) -> MonitorState:
    return MonitorState(
        hist=jnp.full((params.t,), jnp.inf, dtype=dtype),
        count=jnp.zeros((), jnp.int32),
        tag=jnp.full((), tag, jnp.int32),
    )


def record(state: MonitorState, resid: jnp.ndarray) -> MonitorState:
    """Push one residual into the ring buffer.

    Non-finite residuals are clamped to a huge finite sentinel before
    entering the window: a single NaN would otherwise propagate through
    mean/RSD and return NaN metrics FOREVER (every comparison in
    C1/C2/C3 goes False), silently disabling switching for the rest of
    the run -- the one regime where stepping the tag up is the fix
    (DESIGN.md §14).  The sentinel is ``finfo.max ** 0.25`` (~1e77 in
    f64): astronomically above any real relative residual, yet small
    enough that the window mean and the squared deviations in RSD cannot
    overflow to inf.  A breakdown iteration therefore reads as a huge
    residual spike, which is exactly what C1 (stall-with-oscillation)
    keys on.
    """
    t = state.hist.shape[0]
    idx = state.count % t
    r = resid.astype(state.hist.dtype)
    big = jnp.asarray(jnp.finfo(state.hist.dtype).max ** 0.25,
                      state.hist.dtype)
    r = jnp.where(jnp.isfinite(r), r, big)
    return MonitorState(
        hist=state.hist.at[idx].set(r),
        count=state.count + 1,
        tag=state.tag,
    )


def _ordered(state: MonitorState) -> jnp.ndarray:
    """Window ordered oldest -> newest (resid[j-t] ... resid[j-1])."""
    t = state.hist.shape[0]
    return jnp.roll(state.hist, -(state.count % t))


def metrics(state: MonitorState) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(RSD, nDec, relDec) over the trailing window (paper Eq. 3-6)."""
    w = _ordered(state)
    avg = jnp.mean(w)
    # Division guard in the WINDOW's dtype: the literal 1e-300 underflows
    # to 0 in a float32 history buffer, so an all-equal (or tiny) residual
    # window divides 0/0 -> NaN RSD and silently disables condition C1.
    rsd = jnp.sqrt(jnp.mean((w - avg) ** 2)) / jnp.maximum(
        avg, jnp.finfo(w.dtype).tiny
    )
    ndec = jnp.sum((w[:-1] > w[1:]).astype(jnp.int32))
    reldec = (w[0] - w[-1]) / jnp.where(w[0] == 0, 1.0, w[0])
    return rsd, ndec, reldec


def update_tag(state: MonitorState, params: MonitorParams) -> MonitorState:
    """Evaluate the switch conditions; returns state with (possibly) tag+1.

    Only acts when the window is full, ``count >= l``, and ``count % m == 0``
    -- safe to call every iteration inside ``lax.while_loop``.
    """
    t = state.hist.shape[0]
    due = (
        (state.count >= params.l)
        & (state.count >= t)
        & (state.count % params.m == 0)
        & (state.tag < params.max_tag)
    )
    rsd, ndec, reldec = metrics(state)
    c1 = (rsd > params.rsd_limit) & (ndec < params.ndec)
    c2 = (ndec >= params.ndec) & (reldec < params.reldec_limit)
    c3 = ndec == 0
    step = due & (c1 | c2 | c3)
    new_tag = jnp.where(step, state.tag + 1, state.tag)
    return MonitorState(hist=state.hist, count=state.count, tag=new_tag)


# -- per-group sensitivity and promotion (PR 10, DESIGN.md §18) -----------

def group_sensitivity(g, group_size: int = GROUP_SIZE) -> np.ndarray:
    """Per-row-group sensitivity scores from the PACKED magnitudes.

    A low-tag solve plateaus at a true residual ~ ``||(A~ - A) x~||``;
    the decode error is RELATIVE, so the plateau is dominated by the
    largest-magnitude entries.  Carson-Khan's adaptive SPAI (arXiv
    2307.03914) stores entries at precision proportional to magnitude for
    exactly this reason -- the groups holding the biggest entries are the
    ones limiting convergence, and promoting them first buys the most
    plateau for the fewest bytes.

    The score is the max head-only decoded |value| in each group of
    ``group_size`` rows, computed straight from the packed segments
    (head mantissa x shared-exponent scale; no unpack, no tails -- tails
    only refine magnitude below the 15th bit).  Returns an
    ``(n_groups,)`` f64 array aligned with ``TagMap.tags``.
    """
    head = np.asarray(g.head).astype(np.uint32)
    mant = (head & 0x7FFF).astype(np.float64)
    exp_idx = (np.asarray(g.colpak).astype(np.uint64)
               >> np.uint64(32 - g.ei_bit)).astype(np.int64)
    e_sh = np.asarray(g.table, np.int64)[exp_idx] - 1023
    mag = np.ldexp(mant, e_sh - 15)  # |head-only decode|, exact
    groups = np.asarray(g.row_ids, np.int64) // group_size
    n_groups = -(-int(g.shape[0]) // group_size)
    score = np.zeros(n_groups, np.float64)
    np.maximum.at(score, groups, mag)
    return score


def decode_error_scores(g, xhat, group_size: int = GROUP_SIZE) -> np.ndarray:
    """Per-group squared floor contributions at candidate tags 1 and 2.

    A tag-``t`` solve converges (recursively) against the perturbed
    operator ``A~_t`` and plateaus at a TRUE residual
    ``||(A~_t - A) x*|| / ||b||``.  Writing ``E_t = A~_t - A``, the
    plateau decomposes over columns: ``||E_t x*||^2 <= sum_j
    (||E_t[:, j]|| |x*_j|)^2``, and promoting a COLUMN group to tag 3
    zeroes its columns' share exactly (the symmetric induced entry tag
    also zeroes the transposed row-side entries -- free extra margin the
    model conservatively ignores).  The returned ``(2, n_groups)`` array
    holds, per group ``g``, ``sum_{entries e: col(e) in g}
    ((v_t(e) - v3(e)) * xhat[col(e)])^2`` for ``t = 1`` (row 0) and
    ``t = 2`` (row 1); tag 3 contributes 0 by construction.  ``xhat``
    is a per-row solution-magnitude proxy (see ``solvers.adaptive``'s
    preconditioned probe); scores are exact decode errors straight from
    the packed segments.
    """
    from repro.kernels import ref

    xh = np.abs(np.asarray(xhat, np.float64)).reshape(-1)
    g = g.in_csr_order()
    cols = (np.asarray(g.colpak, np.uint32)
            & np.uint32((1 << (32 - g.ei_bit)) - 1)).astype(np.int64)
    v3 = np.asarray(ref.decode_csr_ref(g.colpak, g.head, g.tail1, g.tail2,
                                       g.table, g.ei_bit, 3), np.float64)
    n_groups = -(-int(g.shape[0]) // group_size)
    gc = np.minimum(cols // group_size, n_groups - 1)
    scores = np.zeros((2, n_groups), np.float64)
    for k, t in enumerate((1, 2)):
        vt = np.asarray(ref.decode_csr_ref(g.colpak, g.head, g.tail1,
                                           g.tail2, g.table, g.ei_bit, t),
                        np.float64)
        c = (vt - v3) * xh[cols]
        np.add.at(scores[k], gc, c * c)
    return scores


def map_floor_contrib(scores: np.ndarray, tags: np.ndarray) -> np.ndarray:
    """Per-group floor contribution of a map under ``decode_error_scores``:
    ``scores[tag-1, g]`` for tags 1/2, exactly 0 for tag-3 groups."""
    tags = np.asarray(tags)
    cur = np.zeros(scores.shape[1], np.float64)
    for t in (1, 2):
        sel = tags == t
        cur[sel] = scores[t - 1][sel]
    return cur


def plan_tagmap(scores: np.ndarray, budget: float, tags0=None,
                group_size: int = GROUP_SIZE) -> TagMap:
    """Greedy budget descent over :func:`decode_error_scores`.

    Starting from all-tag-1 (or ``tags0``), repeatedly promote the group
    with the LARGEST current floor contribution one rung until the
    predicted floor ``sqrt(sum_g contrib_g)`` fits inside ``budget``
    (an absolute residual-norm budget, e.g. ``theta * tol * ||b||``).
    The sum is recomputed from scratch each step -- incremental
    subtraction leaves FP rounding residue that can keep a fully
    promoted (provably zero-floor) map "over budget" forever.
    """
    G = np.asarray(scores, np.float64)
    ng = G.shape[1]
    if tags0 is None:
        tags = np.ones(ng, np.uint8)
    else:
        src = tags0.tags if isinstance(tags0, TagMap) else tags0
        tags = np.asarray(src, np.uint8).copy()
        if tags.shape[0] != ng:
            raise ValueError(f"{tags.shape[0]} seed tags for {ng} groups")
    b2 = float(budget) ** 2
    cur = map_floor_contrib(G, tags)
    while cur.sum() > b2:
        open_ = tags < 3
        if not open_.any():
            break
        idx = int(np.argmax(np.where(open_, cur, -np.inf)))
        tags[idx] += 1
        cur = map_floor_contrib(G, tags)
    return TagMap(tags, group_size)


def promote_groups(tm: TagMap, scores: np.ndarray, frac: float = 0.25,
                   step: int = 1) -> TagMap:
    """Promote the top-``frac`` highest-sensitivity UNSATURATED groups.

    The per-group twin of :func:`update_tag`'s whole-operator step: when
    the monitor (or the host driver's stall check) says the current
    precision is limiting convergence, only the groups most responsible
    -- highest :func:`group_sensitivity` score, tag < 3 -- step up.
    Returns a NEW map (at least one group promotes if any is
    unsaturated, so escalation always makes progress).
    """
    scores = np.asarray(scores, np.float64)
    if scores.shape[0] != tm.n_groups:
        raise ValueError(
            f"{scores.shape[0]} scores for {tm.n_groups} groups"
        )
    open_idx = np.nonzero(tm.tags < 3)[0]
    if open_idx.size == 0:
        return tm
    n = max(1, int(round(frac * tm.n_groups)))
    n = min(n, open_idx.size)
    top = open_idx[np.argsort(-scores[open_idx], kind="stable")[:n]]
    return tm.promoted(top, step=step)


def stalled(prev_relres: float, relres: float, iters: int,
            reldec_limit: float = 0.45) -> bool:
    """Host-side chunk-granularity stall test: the driver's mirror of
    condition C2 (decreasing, but too slowly).

    ``prev_relres`` -> ``relres`` over ``iters`` iterations is a stall
    when the per-chunk relative decrease misses ``reldec_limit`` --
    including the non-finite and non-decreasing cases C1/C3 subsume.
    """
    if iters <= 0:
        return False
    if not np.isfinite(relres):
        return True
    if not np.isfinite(prev_relres) or prev_relres <= 0:
        return False
    return (prev_relres - relres) / prev_relres < reldec_limit
