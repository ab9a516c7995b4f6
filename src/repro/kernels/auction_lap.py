"""Pallas TPU kernel: batched auction algorithm for the assignment problem.

Exact q-Wasserstein between persistence diagrams is a min-cost perfect
matching on the diagonal-augmented cost matrix — historically a host-side
O(n³) Hungarian solve (``repro.metrics.reference``), which caps exact
distances at "small diagrams, one pair at a time".  The auction algorithm
(Bertsekas; the synchronous/Jacobi variant of Bertsekas–Castañón) is the
accelerator-friendly formulation: every free person bids simultaneously
(two row-max reductions + one object-side argmax aggregation per round —
pure VPU work on an (M, M) value matrix), objects go to the highest
bidder, and ε-scaling anneals the bid increment so late rounds only refine
an almost-optimal price vector.

One grid step solves one pair's matrix, held in VMEM for the whole
data-dependent bidding loop (the ``gf2_reduce`` pattern); batching over
pairs is the leading grid axis.  The kernel body and the pure-jnp oracle
(``repro.kernels.ref.auction_lap_ref``) share ``auction_solve`` below, so
kernel-vs-reference parity is semantic, not coincidental.

ε-scaling + termination contract
--------------------------------
Costs are normalized by their per-pair max, so prices live in O(1) float32
territory; the ladder anneals ``eps0 → eps0·factor^-(n_scales-1)``
(default 0.25 → ~1.3e-7) and each assignment found at scale ε is within
``M·ε·max|cost|`` of optimal total cost.  The final scale's increments sit
just above f32 price resolution — in practice the assignment is *exactly*
optimal for non-degenerate inputs (asserted against the Hungarian oracle
in tests and ``metrics_bench``), and ties (e.g. the all-zero
reservoir↔reservoir block of diagram matrices) only ever differ in which
of several equal-cost matchings is returned.  A per-scale round cap plus a
deterministic index-order completion of any still-free rows guarantee the
kernel always returns a perfect matching; ``converged`` reports whether
the reported matching came from one of the two finest ε rungs (the tight
suboptimality guarantee).

Collapsed (reservoir-free) formulation
--------------------------------------
The diagram matrices this kernel exists for are *degenerate*: half the
rows/columns are identical diagonal reservoirs, and the M-way ties make
the reservoir block fight over equal-cost slots for hundreds of rounds.
``auction_solve_collapsed`` solves the same optimum on the K×K *reduced*
costs ``cbar[i, j] = cost(i→j) − cost(i→Δ) − cost(Δ→j)`` plus ONE
pseudo-object ``OUT`` (price fixed at 0, unlimited capacity — the whole
reservoir block collapsed into a single multi-unit slot, the
transportation-auction variant), so no reservoir tie ever reaches the
bidding loop.  Because the collapsed problem is *asymmetric* (persons may
stay OUT, objects may stay unmatched), the per-scale loop is a **combined
forward/reverse auction**: forward rounds have free persons bid (OUT is
always a zero-value fallback), reverse rounds have unmatched objects with
stale positive prices bid for persons through the profit vector ``pi`` —
the classic repair for prices stranded above the λ = 0 floor by scale
resets or warm starts, without which ε-scaling loses its optimality
guarantee on asymmetric problems.  Warm starts enter as ``price0``
(max-normalized units, what the solver also returns): any nonnegative
price vector is safe — the reverse phase re-grounds stale prices — which
is what makes the serve-level LSH-bucket price cache sound.  A warm lane
(any nonzero ``price0``) additionally skips the annealing ladder and runs
straight at the finest ε — coarse scales would only inflate the
already-equilibrated prices and then pay reverse rounds undoing it —
which is where the measured warm-repeat round reduction comes from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_EPS0 = 0.25
DEFAULT_EPS_FACTOR = 5.0
DEFAULT_N_SCALES = 10
DEFAULT_REV_EVERY = 8

# collapsed-assignment code for "person matched to the collapsed diagonal
# reservoir" (the OUT pseudo-object); -1 keeps meaning "free"
OUT = -2


def eps_ladder_values(eps0: float, eps_factor: float,
                      n_scales: int) -> list[float]:
    """The ε ladder ``eps0·factor^-s`` as f32-exact Python floats.

    Computed once on the host, so every program (the Pallas kernel, the
    jnp oracle, either one interpreted or compiled) bakes in the same
    literals: an in-program ``pow`` is folded by XLA in one context and
    evaluated at run time in another, which can move ε — and with it every
    price — by an ulp.
    """
    f = np.float32
    return [float(f(eps0) * f(eps_factor) ** f(-s)) for s in range(n_scales)]


def _ladder(scale_idx, values):
    """Per-scale ε over ``scale_idx`` from literal ``values`` (selects,
    not a captured array: a Pallas kernel may close over scalars only)."""
    out = jnp.full(scale_idx.shape, values[0], jnp.float32)
    for s, v in enumerate(values[1:], 1):
        out = jnp.where(scale_idx == s, v, out)
    return out


def _sum_in_order(terms):
    """Σ over the last axis, left to right, kept as a ``(..., 1)`` column.

    Each term is extracted exactly (a one-hot sum) and added in index
    order: a tree reduction's association depends on the program it is
    fused into, and the kernel and the oracle must agree to the bit.
    """
    lane = lax.broadcasted_iota(jnp.int32, terms.shape, terms.ndim - 1)
    total = jnp.zeros(terms.shape[:-1] + (1,), terms.dtype)
    for i in range(terms.shape[-1]):
        total = total + jnp.sum(jnp.where(lane == i, terms, 0.0), axis=-1,
                                keepdims=True)
    return total


def default_max_rounds(m: int) -> int:
    """Per-scale bidding-round cap — the one definition the kernel wrapper
    and the jnp oracle share, so their fallback behavior is identical."""
    return 64 + 32 * m


def bid_round(neg_cost, price, p2o, o2p, eps):
    """One synchronous (Jacobi) auction round.

    ``neg_cost``: (M, M) benefit = −cost; ``price``: (M,) object prices;
    ``p2o``/``o2p``: person→object / object→person assignment (−1 = free).
    Every free person bids best-value + ε over its second-best; each object
    receiving bids goes to the highest bidder (ties → lowest person index),
    evicting any previous owner.
    """
    m = neg_cost.shape[-1]
    idx = jnp.arange(m)
    free = p2o < 0
    v = neg_cost - price[None, :]
    j_star = jnp.argmax(v, axis=-1)
    v1 = jnp.max(v, axis=-1)
    v2 = jnp.max(jnp.where(idx[None, :] == j_star[:, None], -jnp.inf, v),
                 axis=-1)
    v2 = jnp.where(jnp.isfinite(v2), v2, v1)  # M == 1 degenerate case
    # price[j*] + (v1 − v2) + ε == a[i, j*] − v2 + ε
    bid = (jnp.take_along_axis(neg_cost, j_star[:, None], axis=-1)[:, 0]
           - v2 + eps)
    bids = jnp.where(free[:, None] & (j_star[:, None] == idx[None, :]),
                     bid[:, None], -jnp.inf)          # (person, object)
    best = jnp.max(bids, axis=0)
    winner = jnp.argmax(bids, axis=0)
    has = best > -jnp.inf
    price = jnp.where(has, best, price)
    # owners of re-auctioned objects are evicted ...
    lost = jnp.any(has[None, :] & (o2p[None, :] == idx[:, None]), axis=-1)
    p2o = jnp.where(lost, -1, p2o)
    o2p = jnp.where(has, winner, o2p)
    # ... and each winning bidder picks up its (single) object
    won = jnp.max(jnp.where(has[None, :] & (winner[None, :] == idx[:, None]),
                            idx[None, :], -1), axis=-1)
    p2o = jnp.where(won >= 0, won, p2o)
    return price, p2o, o2p


def auction_solve(cost, eps0: float = DEFAULT_EPS0,
                  eps_factor: float = DEFAULT_EPS_FACTOR,
                  n_scales: int = DEFAULT_N_SCALES,
                  max_rounds: int | None = None):
    """Solve one (M, M) assignment problem by ε-scaled Jacobi auction.

    Returns ``(assign, total, converged, rounds)``: ``assign[i]`` = column
    matched to row i (always a permutation), ``total`` = Σ cost[i,
    assign[i]] of the found matching (computed from the *unnormalized*
    costs, full precision), ``rounds`` = total bidding rounds across all
    scales.  The reported assignment is the finest fully-converged scale's;
    ``converged`` is True only when that scale is one of the **two finest**
    ε rungs (suboptimality ≤ M·ε_factor·ε_final·max|cost| — the f32 stall
    on the last rung keeps the guarantee, a coarse-only convergence does
    not and reports False).
    """
    m = cost.shape[-1]
    if max_rounds is None:
        max_rounds = default_max_rounds(m)
    cost = cost.astype(jnp.float32)
    c_scale = jnp.maximum(jnp.max(jnp.abs(cost)), 1e-30)
    a = -(cost / c_scale)
    idx = jnp.arange(m)
    eps_ladder = _ladder(jnp.arange(n_scales),
                         eps_ladder_values(eps0, eps_factor, n_scales))

    def run_scale(carry, eps):
        price, p2o, o2p, rounds = carry
        # partial reset (ε-CS): keep assignments still within eps of each
        # person's best value at the new scale — the warm start that makes
        # late scales cheap refinements instead of full re-auctions
        v = a - price[None, :]
        best = jnp.max(v, axis=-1)
        mine = jnp.take_along_axis(v, jnp.clip(p2o, 0)[:, None], axis=-1)[:, 0]
        keep = (p2o >= 0) & (mine >= best - eps)
        p2o = jnp.where(keep, p2o, -1)
        o2p = jnp.max(jnp.where(keep[:, None] & (p2o[:, None] == idx[None, :]),
                                idx[:, None], -1), axis=0)

        def cond(s):
            _, p2o, _, it, stalled = s
            return jnp.any(p2o < 0) & (it < max_rounds) & ~stalled

        def body(s):
            price, p2o, o2p, it, _ = s
            price2, p2o2, o2p2 = bid_round(a, price, p2o, o2p, eps)
            # every win must raise a price by >= eps; an unchanged price
            # vector means the increments fell below f32 resolution and no
            # further round can make progress (livelock) — bail out and let
            # the last converged scale's assignment stand
            stalled = jnp.all(price2 == price)
            return price2, p2o2, o2p2, it + 1, stalled

        price, p2o, o2p, it, _ = lax.while_loop(
            cond, body, (price, p2o, o2p, jnp.int32(0), jnp.bool_(False)))
        return (price, p2o, o2p, rounds + it), (p2o, jnp.all(p2o >= 0))

    free = jnp.full((m,), -1, jnp.int32)
    (price, _, _, rounds), (p2o_s, conv_s) = lax.scan(
        run_scale, (jnp.zeros((m,), jnp.float32), free, free, jnp.int32(0)),
        eps_ladder)
    # use the finest-ε scale that fully converged (stalled/capped scales
    # carry partial assignments); the optimality flag demands that scale be
    # one of the two finest rungs — see the docstring
    any_conv = jnp.any(conv_s)
    converged = jnp.any(conv_s[-2:])
    last = n_scales - 1 - jnp.argmax(conv_s[::-1])
    p2o = jnp.where(any_conv, jnp.take(p2o_s, last, axis=0), p2o_s[-1])
    # deterministic completion of any still-free rows (nothing converged):
    # k-th free person ↔ k-th free object, so a permutation always returns
    owned = jnp.any((p2o[:, None] == idx[None, :]) & (p2o >= 0)[:, None],
                    axis=0)
    free_p, free_o = p2o < 0, ~owned
    rank_p = jnp.cumsum(free_p) - 1
    rank_o = jnp.cumsum(free_o) - 1
    match = (free_p[:, None] & free_o[None, :]
             & (rank_p[:, None] == rank_o[None, :]))
    fill = jnp.max(jnp.where(match, idx[None, :], -1), axis=-1)
    assign = jnp.where(free_p, fill, p2o)
    total = _sum_in_order(
        jnp.take_along_axis(cost, assign[:, None], axis=-1)[:, 0])[0]
    return assign, total, converged, rounds


# --------------------------------------------------------------------------
# collapsed (reservoir-free) forward/reverse auction
# --------------------------------------------------------------------------

def _pick(x, j, axis):
    """``x``'s entry at index ``j`` along ``axis`` (``j`` has x's shape
    without that axis).  A one-hot max, bit-identical to
    ``take_along_axis`` on floats: Mosaic lowers a gather only in its 2-D
    same-shape form, so the collapsed solver never emits one.
    """
    pos = lax.broadcasted_iota(jnp.int32, x.shape, axis % x.ndim)
    hit = pos == jnp.expand_dims(j, axis)
    return jnp.max(jnp.where(hit, x, -jnp.inf), axis=axis)


def _col(x):
    """(..., K) -> (..., K, 1); bools go through int32, since Mosaic has
    no lane-to-sublane reshape of an i1 vector."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32)[..., :, None] != 0
    return x[..., :, None]


def _row(x):
    """(..., K) -> (..., 1, K), with bools through int32 as in :func:`_col`."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32)[..., None, :] != 0
    return x[..., None, :]


def _grids(k):
    """(K, K) person (row) and object (column) index planes."""
    return (lax.broadcasted_iota(jnp.int32, (k, k), 0),
            lax.broadcasted_iota(jnp.int32, (k, k), 1))


# The collapsed rounds and solver take a leading pair axis on every operand
# — ``a`` (B, K, K), vectors (B, K), per-pair scalars (B, 1) — and never a
# 1-D vector, a gather, a batched ``cond`` or a scan with per-step outputs:
# the forms Mosaic refuses.  One batched definition serves the Pallas
# kernel (B = tile_b) and, through ``auction_solve_collapsed``, the jnp
# oracle, so the two stay bit-identical.

def collapsed_bid_round(a, price, pi, p2o, o2p, eps):
    """One synchronous *forward* round of the collapsed auction.

    ``a``: (B, K, K) benefit = −reduced-cost, ``-inf`` at invalid pairs;
    ``price``: (B, K) real-object prices; ``pi``: (B, K) person profits;
    ``p2o`` ∈ {OUT, −1=free, j}; ``o2p`` ∈ {−1=unowned, i}; ``eps`` (B, 1).
    Every free person's option set is its real objects *plus* OUT (value
    0, price pinned at 0, unlimited capacity): persons whose best real
    value is ≤ 0 take OUT immediately — the collapsed reservoir absorbs
    any number of takers in one round, which is exactly the tie blowup the
    expanded matrix pays ~M rounds for — and the rest bid
    best-over-second-best + ε with OUT folded into the second-best.
    """
    row, col = _grids(a.shape[-1])
    free = p2o == -1
    v = a - price[..., None, :]
    j_star = jnp.argmax(v, axis=-1)
    v1 = jnp.max(v, axis=-1)
    v2 = jnp.max(jnp.where(col == j_star[..., :, None], -jnp.inf, v),
                 axis=-1)
    v2o = jnp.maximum(v2, 0.0)         # second-best option including OUT
    take_out = free & (v1 <= 0.0)      # OUT is (weakly) the best option
    bid_ok = free & (v1 > 0.0)
    bid = _pick(a, j_star, -1) - v2o + eps
    bids = jnp.where(_col(bid_ok) & (j_star[..., :, None] == col),
                     bid[..., :, None], -jnp.inf)     # (person, object)
    best = jnp.max(bids, axis=-2)
    winner = jnp.argmax(bids, axis=-2)
    has = best > -jnp.inf
    price = jnp.where(has, best, price)
    lost = jnp.any(_row(has) & (o2p[..., None, :] == row), axis=-1)
    p2o = jnp.where(lost, -1, p2o)
    o2p = jnp.where(has, winner, o2p)
    won = jnp.max(jnp.where(_row(has) & (winner[..., None, :] == row),
                            col, -1), axis=-1)
    p2o = jnp.where(won >= 0, won, p2o)
    # winners' profits: value of the second-best option they forwent, −ε —
    # the ε-CS-consistent dual update the reverse rounds price against
    pi = jnp.where(won >= 0, v2o - eps, pi)
    pi = jnp.where(take_out, 0.0, pi)
    p2o = jnp.where(take_out, OUT, p2o)
    return price, pi, p2o, o2p


def collapsed_reverse_round(a, price, pi, p2o, o2p, keep2, eps):
    """One synchronous *reverse* round: stale unmatched objects bid.

    Bidders are real objects that are unowned yet priced above the λ = 0
    floor (stranded there by a scale-boundary reset or a warm-start price
    vector).  Each computes its best person through the profit vector
    (``β1 = max_i a[i,j] − pi[i]``): below ``λ + ε`` it *drops out*
    (price := 0, the state the termination test accepts); otherwise it
    undercuts to ``max(λ, β2 − ε)`` and offers that person a raised
    profit.  A person receiving several offers accepts the best one
    (Jacobi conflict resolution — losers keep their old price and retry),
    and the accepted person's previous object is released with its price
    intact, to be repaired by a later reverse round.  Shapes as in
    :func:`collapsed_bid_round`.
    """
    row, col = _grids(a.shape[-1])
    bidder = keep2 & (o2p < 0) & (price > 0.0)
    w = a - pi[..., :, None]           # (person, object)
    i_star = jnp.argmax(w, axis=-2)
    b1 = jnp.max(w, axis=-2)
    b2 = jnp.max(jnp.where(row == i_star[..., None, :], -jnp.inf, w),
                 axis=-2)
    drop = bidder & (b1 < eps)
    active = bidder & (b1 >= eps)
    p_new = jnp.maximum(0.0, b2 - eps)
    offer = _pick(a, i_star, -2) - p_new
    offers = jnp.where(_row(active) & (i_star[..., None, :] == row),
                       offer[..., None, :], -jnp.inf)  # (person, object)
    best_off = jnp.max(offers, axis=-1)
    j_win = jnp.argmax(offers, axis=-1)
    got = best_off > -jnp.inf
    # accepted persons release their old object (an owned object is never
    # a bidder, so freed/taken are disjoint and update order is immaterial)
    freed = jnp.any(_col(got) & (p2o[..., :, None] == col), axis=-2)
    won_obj = _col(got) & (j_win[..., :, None] == col)
    taken = jnp.any(won_obj, axis=-2)
    new_owner = jnp.max(jnp.where(won_obj, row, -1), axis=-2)
    o2p = jnp.where(freed, -1, o2p)
    o2p = jnp.where(taken, new_owner, o2p)
    price = jnp.where(taken, p_new, jnp.where(drop, 0.0, price))
    p2o = jnp.where(got, j_win, p2o)
    pi = jnp.where(got, best_off, pi)
    return price, pi, p2o, o2p


def solve_collapsed_batch(cbar, keep1, keep2, price0,
                          eps0: float = DEFAULT_EPS0,
                          eps_factor: float = DEFAULT_EPS_FACTOR,
                          n_scales: int = DEFAULT_N_SCALES,
                          max_rounds: int | None = None,
                          rev_every: int = DEFAULT_REV_EVERY):
    """:func:`auction_solve_collapsed` over a leading pair axis.

    ``cbar`` (B, K, K), ``keep1``/``keep2``/``price0`` (B, K).  Returns
    ``(p2o (B, K), total (B, 1), converged (B, 1), rounds (B, 1),
    price (B, K))``.  Each pair's bidding loop runs until that pair stops,
    exactly as ``vmap`` of the one-pair solver would: the loop runs while
    any pair is live and a finished pair's state is held.
    """
    k = cbar.shape[-1]
    if max_rounds is None:
        max_rounds = default_max_rounds(k)
    rev_every = int(rev_every)
    cbar = cbar.astype(jnp.float32)
    valid = _col(keep1) & _row(keep2)
    c_scale = jnp.maximum(jnp.max(jnp.max(
        jnp.where(valid, jnp.abs(cbar), 0.0), axis=-1), axis=-1,
        keepdims=True), 1e-30)[..., None]
    a = jnp.where(valid, -(cbar / c_scale), -jnp.inf)
    scale_idx = lax.broadcasted_iota(jnp.int32, (1, n_scales), 1)
    ladder = eps_ladder_values(eps0, eps_factor, n_scales)
    eps_ladder = _ladder(scale_idx, ladder)
    price = jnp.where(keep2, jnp.maximum(price0.astype(jnp.float32), 0.0),
                      0.0)
    # warm start (any nonzero price) skips the annealing ladder: coarse
    # scales would inflate the already-equilibrated prices and then pay
    # reverse rounds to re-ground them, so a warm lane runs every scale
    # iteration at the finest ε instead (auction from arbitrary nonneg
    # prices + empty assignment preserves ε-CS, so the ε_final optimality
    # certificate is unchanged; the ladder is purely a cold-start speedup)
    eps_ladder = jnp.where(jnp.any(price > 0.0, axis=-1, keepdims=True),
                           ladder[-1], eps_ladder)
    # initial profits must over-claim nothing: best attainable value now
    pi = jnp.maximum(jnp.max(a - price[..., None, :], axis=-1), 0.0)
    # invalid persons sit at OUT for good (cbar row is -inf, never bid)
    p2o = jnp.where(keep1, -1, OUT).astype(jnp.int32)
    o2p = jnp.full(p2o.shape, -1, jnp.int32)
    row, col = _grids(k)
    # per-pair counters and flags are int32 columns: Mosaic cannot carry
    # an i1 vector through a loop
    zero = jnp.zeros(p2o.shape[:-1] + (1,), jnp.int32)

    def stale(price, o2p):
        return jnp.any(keep2 & (o2p < 0) & (price > 0.0), axis=-1,
                       keepdims=True)

    def any_free(p2o):
        return jnp.any(p2o == -1, axis=-1, keepdims=True)

    def run_scale(sc, carry):
        price, pi, p2o, o2p, rounds, sel, any_conv, late_conv = carry
        eps = jnp.max(jnp.where(scale_idx == sc, eps_ladder, -jnp.inf),
                      axis=-1, keepdims=True)
        # ε-CS partial reset: persons keep their slot (real object or OUT)
        # only while it is still within eps of their best option at the
        # new, finer scale; freed persons re-bid, and the objects they
        # abandon keep their stale prices for the reverse rounds to repair
        v = a - price[..., None, :]
        best = jnp.maximum(jnp.max(v, axis=-1), 0.0)
        mine = jnp.where(p2o >= 0, _pick(v, jnp.clip(p2o, 0), -1),
                         0.0)                        # OUT is worth exactly 0
        keep = (p2o != -1) & (mine >= best - eps)
        keep = keep | ~keep1
        p2o = jnp.where(keep, p2o, -1)
        o2p = jnp.max(jnp.where(p2o[..., :, None] == col, row, -1), axis=-2)

        def live(s):
            price, pi, p2o, o2p, prev, it, stalled = s
            return ((any_free(p2o) | stale(price, o2p))
                    & (it < max_rounds) & (stalled == 0))

        def body(s):
            price, pi, p2o, o2p, prev, it, _ = s
            free_any = any_free(p2o)
            if rev_every > 0:
                periodic = (it % rev_every) == (rev_every - 1)
            else:
                periodic = zero < 0
            do_rev = stale(price, o2p) & (~free_any | periodic)
            fwd = collapsed_bid_round(a, price, pi, p2o, o2p, eps)
            rev = collapsed_reverse_round(a, price, pi, p2o, o2p, keep2, eps)
            price2, pi2, p2o2, o2p2 = (jnp.where(do_rev, r, f)
                                       for r, f in zip(rev, fwd))
            # two livelock exits, both leaving the last converged scale's
            # assignment to stand: an unchanged state means the ≥ε
            # increments fell below f32 resolution, and a state equal to
            # the one *two* rounds back means a forced fwd/rev interleave
            # (rev_every) is ping-ponging a contested object ±ε per phase
            # — neither can ever make further progress
            p_price, p_pi, p_p2o = prev
            same1 = jnp.all((price2 == price) & (pi2 == pi) & (p2o2 == p2o),
                            axis=-1, keepdims=True)
            same2 = jnp.all((price2 == p_price) & (pi2 == p_pi)
                            & (p2o2 == p_p2o), axis=-1, keepdims=True)
            new = (price2, pi2, p2o2, o2p2, (price, pi, p2o), it + 1,
                   (same1 | same2).astype(jnp.int32))
            on = live(s)
            return jax.tree.map(lambda n, o: jnp.where(on, n, o), new, s)

        prev0 = (jnp.full_like(price, -1.0), jnp.full_like(pi, -1.0),
                 jnp.full_like(p2o, -3))
        price, pi, p2o, o2p, _, it, _ = lax.while_loop(
            lambda s: jnp.any(live(s)), body,
            (price, pi, p2o, o2p, prev0, zero, zero))
        conv = ~any_free(p2o) & ~stale(price, o2p)
        # the finest converged scale's assignment so far, and whether one
        # of the two finest rungs converged (carried, not stacked: Mosaic
        # lowers only scans without per-step inputs or outputs)
        sel = jnp.where(conv, p2o, sel)
        late = conv & (sc >= n_scales - 2)
        return (price, pi, p2o, o2p, rounds + it, sel,
                any_conv | conv.astype(jnp.int32),
                late_conv | late.astype(jnp.int32))

    (price, _, p2o_last, _, rounds, sel, any_conv, converged) = lax.fori_loop(
        0, n_scales, run_scale,
        (price, pi, p2o, o2p, zero, p2o, zero, zero))
    p2o = jnp.where(any_conv != 0, sel, p2o_last)
    # a still-free person (nothing converged) is reported at OUT: the
    # matching stays feasible — every person holds at most one distinct
    # object throughout — just not certified optimal (converged=False)
    matched = p2o >= 0
    total = _sum_in_order(
        jnp.where(matched, _pick(cbar, jnp.clip(p2o, 0), -1), 0.0))
    return p2o, total, converged != 0, rounds, price


def auction_solve_collapsed(cbar, keep1, keep2, price0=None,
                            eps0: float = DEFAULT_EPS0,
                            eps_factor: float = DEFAULT_EPS_FACTOR,
                            n_scales: int = DEFAULT_N_SCALES,
                            max_rounds: int | None = None,
                            rev_every: int = DEFAULT_REV_EVERY):
    """ε-scaled combined forward/reverse auction on one collapsed problem.

    ``cbar`` is the (K, K) *reduced* cost (matching pair (i, j) instead of
    sending both to the diagonal), ``keep1``/``keep2`` the valid-slot
    masks, ``price0`` an optional warm-start price vector in the solver's
    max-normalized units (any nonnegative vector is safe, and a nonzero
    one skips the ε ladder — see the module docstring).  Returns
    ``(p2o, total, converged, rounds, price)``:
    ``p2o[i]`` ∈ {OUT, −1, j} with ``total = Σ cbar[i, p2o[i]]`` over the
    matched pairs (add the caller's diagonal base cost to recover the
    expanded-matrix optimum), ``converged`` as in :func:`auction_solve`
    (one of the two finest ε rungs fully terminated: no free person, no
    unmatched object priced above 0), ``price`` the final normalized
    prices (feed them back as ``price0`` to warm-start a near-duplicate
    pair).  ``rev_every`` > 0 additionally forces a reverse round every
    that many rounds even while free persons remain (the fwd/rev phase
    ratio the autotuner sweeps); reverse rounds always run once forward
    bidding has no free persons left.
    """
    if price0 is None:
        price0 = jnp.zeros(keep2.shape, jnp.float32)
    p2o, total, conv, rounds, price = solve_collapsed_batch(
        cbar[None], keep1[None], keep2[None], price0[None], eps0=eps0,
        eps_factor=eps_factor, n_scales=n_scales, max_rounds=max_rounds,
        rev_every=rev_every)
    return (p2o[0].astype(jnp.int32), total[0, 0], conv[0, 0],
            rounds[0, 0], price[0])


def expand_collapsed_assignment(p2o, keep1, keep2):
    """(K,) collapsed assignment → (2K,) expanded-matrix row assignment.

    Rows 0..K−1 are the real D1 slots, rows K..2K−1 the reservoirs (the
    ``metrics/exact.py::augmented_cost`` convention).  A person at OUT (or
    free, or invalid) pairs with its own reservoir column K+i; a real
    column nobody owns pairs with its own reservoir row K+j; leftover
    reservoir rows/columns pair off in index order (all zero-cost).  The
    result evaluates the *expanded* cost matrix to exactly
    ``base + Σ cbar[i, p2o[i]]`` — the bit-for-bit equivalence the
    degenerate-input tests assert.
    """
    k = p2o.shape[-1]
    idx = jnp.arange(k)
    matched = p2o >= 0
    top = jnp.where(matched, p2o, k + idx)
    owned = jnp.any(matched[:, None] & (p2o[:, None] == idx[None, :]), axis=0)
    # reservoir row K+j takes column j when unowned; owned columns leave
    # their reservoir rows to pair with the reservoir columns K+i of
    # matched persons (rank pairing, #owned == #matched)
    rank_r = jnp.cumsum(owned) - 1
    rank_c = jnp.cumsum(matched) - 1
    pair = (owned[:, None] & matched[None, :]
            & (rank_r[:, None] == rank_c[None, :]))
    fill = jnp.max(jnp.where(pair, k + idx[None, :], -1), axis=-1)
    bottom = jnp.where(owned, fill, idx)
    return jnp.concatenate([top, bottom]).astype(jnp.int32)


def _kernel(cost_ref, assign_ref, total_ref, conv_ref, rounds_ref, *,
            eps0, eps_factor, n_scales, max_rounds):
    assign, total, converged, rounds = jax.vmap(functools.partial(
        auction_solve, eps0=eps0, eps_factor=eps_factor, n_scales=n_scales,
        max_rounds=max_rounds))(cost_ref[...])
    assign_ref[...] = assign.astype(jnp.int32)
    total_ref[...] = total[:, None]
    conv_ref[...] = converged[:, None]
    rounds_ref[...] = rounds[:, None].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "eps0", "eps_factor", "n_scales", "max_rounds", "tile_b", "interpret"))
def auction_lap_pallas(cost: jax.Array, eps0: float = DEFAULT_EPS0,
                       eps_factor: float = DEFAULT_EPS_FACTOR,
                       n_scales: int = DEFAULT_N_SCALES,
                       max_rounds: int | None = None,
                       tile_b: int = 1,
                       *, interpret: bool):
    """Batched assignment solve: (B, M, M) costs → matchings + totals.

    Returns ``(assign (B, M) i32, total (B,) f32, converged (B,) bool,
    rounds (B,) i32)``.  ``tile_b`` pairs are solved per grid step (their
    cost matrices co-resident in VMEM for the entire data-dependent
    bidding loop; the batch is zero-padded to a ``tile_b`` multiple —
    an all-zero cost matrix converges in a handful of rounds).  The
    autotuner (``python -m repro.perfgate tune``) sweeps ``tile_b``; the
    ops wrapper loads the pinned winner per device.
    """
    b, m, m2 = cost.shape
    if m != m2:
        raise ValueError(f"cost must be square per pair, got {(m, m2)}")
    if max_rounds is None:
        max_rounds = default_max_rounds(m)
    bp = -(-b // tile_b) * tile_b
    costp = jnp.pad(cost.astype(jnp.float32),
                    ((0, bp - b), (0, 0), (0, 0)))
    assign, total, conv, rounds = pl.pallas_call(
        functools.partial(_kernel, eps0=eps0, eps_factor=eps_factor,
                          n_scales=n_scales, max_rounds=max_rounds),
        grid=(bp // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, m, m), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((tile_b, m), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, m), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.bool_),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
        name="auction_lap",
    )(costp)
    return assign[:b], total[:b, 0], conv[:b, 0], rounds[:b, 0]


def _collapsed_kernel(cbar_ref, keep1_ref, keep2_ref, price0_ref,
                      p2o_ref, total_ref, conv_ref, rounds_ref, price_ref, *,
                      eps0, eps_factor, n_scales, max_rounds, rev_every):
    # per-pair rows arrive as (tile_b, 1, K) blocks (last two dims equal
    # the array's, as Mosaic requires) and masks as int32 (no bool refs)
    p2o, total, conv, rounds, price = solve_collapsed_batch(
        cbar_ref[...], keep1_ref[:, 0, :] != 0, keep2_ref[:, 0, :] != 0,
        price0_ref[:, 0, :], eps0=eps0, eps_factor=eps_factor,
        n_scales=n_scales, max_rounds=max_rounds, rev_every=rev_every)
    p2o_ref[:, 0, :] = p2o
    total_ref[:, 0, :] = total
    conv_ref[:, 0, :] = conv.astype(jnp.int32)
    rounds_ref[:, 0, :] = rounds
    price_ref[:, 0, :] = price


@functools.partial(jax.jit, static_argnames=(
    "eps0", "eps_factor", "n_scales", "max_rounds", "rev_every", "tile_b",
    "interpret"))
def auction_lap_collapsed_pallas(cbar: jax.Array, keep1: jax.Array,
                                 keep2: jax.Array, price0: jax.Array,
                                 eps0: float = DEFAULT_EPS0,
                                 eps_factor: float = DEFAULT_EPS_FACTOR,
                                 n_scales: int = DEFAULT_N_SCALES,
                                 max_rounds: int | None = None,
                                 rev_every: int = DEFAULT_REV_EVERY,
                                 tile_b: int = 1,
                                 *, interpret: bool):
    """Batched collapsed forward/reverse auction: (B, K, K) reduced costs.

    Returns ``(p2o (B, K) i32, total (B,) f32, converged (B,) bool,
    rounds (B,) i32, price (B, K) f32)`` — see
    :func:`auction_solve_collapsed` for the contract.  ``tile_b`` pairs
    co-reside in VMEM per grid step exactly like ``auction_lap_pallas``;
    batch padding uses all-invalid slots, which terminate in zero rounds
    (every padded person starts at OUT).
    """
    b, k, k2 = cbar.shape
    if k != k2:
        raise ValueError(f"cbar must be square per pair, got {(k, k2)}")
    if keep1.shape != (b, k) or keep2.shape != (b, k):
        raise ValueError(
            f"keep masks must be {(b, k)}, got {keep1.shape}/{keep2.shape}")
    if price0.shape != (b, k):
        raise ValueError(f"price0 must be {(b, k)}, got {price0.shape}")
    if max_rounds is None:
        max_rounds = default_max_rounds(k)
    bp = -(-b // tile_b) * tile_b
    pad_b = ((0, bp - b),)

    def rows(x, dtype):  # (B, K) -> padded (Bp, 1, K)
        return jnp.pad(x.astype(dtype), pad_b + ((0, 0),))[:, None, :]

    row_spec = pl.BlockSpec((tile_b, 1, k), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    one_spec = pl.BlockSpec((tile_b, 1, 1), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    p2o, total, conv, rounds, price = pl.pallas_call(
        functools.partial(_collapsed_kernel, eps0=eps0,
                          eps_factor=eps_factor, n_scales=n_scales,
                          max_rounds=max_rounds, rev_every=rev_every),
        grid=(bp // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, k, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            row_spec, row_spec, row_spec,
        ],
        out_specs=[row_spec, one_spec, one_spec, one_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1, k), jnp.float32),
        ],
        interpret=interpret,
        name="auction_lap_collapsed",
    )(jnp.pad(cbar.astype(jnp.float32), pad_b + ((0, 0), (0, 0))),
      rows(keep1, jnp.int32), rows(keep2, jnp.int32),
      rows(price0, jnp.float32))
    p2o, total, conv, rounds, price = (
        x[:b, 0] for x in (p2o, total, conv, rounds, price))
    return p2o, total[:, 0], conv[:, 0] != 0, rounds[:, 0], price
