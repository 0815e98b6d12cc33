"""Pallas TPU kernel: auction score computed IN-KERNEL + fused min2.

The auction round's hot op is a per-row (min, argmin, second-min) over
the priced score matrix ``score[P, N] + price[N]``.  With the score
materialized (ops/reduce2.py), every round pays a full HBM sweep of the
biggest tensor in the solver, plus one sweep to write it per slot.

But the score is a FUNCTION of tiny inputs: [N] vectors (fill factor,
weights, validity, price, candidate group ids) and [P, few] id columns
(previous holders, exclusivity list, rule anchors).  This kernel
evaluates the score formula per (TILE_P, TILE_N) block in VMEM —
identical term-by-term to the matrix build in plan/tensor.py
run_auction — and reduces it on the fly.  Per-round HBM traffic drops
from O(P*N) to O(P + N): the matrix never exists.

Outputs per row: (best = min of score+price, choice = argmin, second =
second-best, raw = unpriced score at choice) — the exact tuple
_assign_slot's rounds consume.  Tie-breaks match ops/reduce2.py: lowest
index wins within and across tiles.

Correctness is pinned by tests/test_score_fused.py: interpret-mode runs
of this kernel against the reference matrix formula, term order
preserved; chip_smoke.py's kernels phase checks the compiled kernel
against the interpreted one on a chip batch with ties.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiles import tile_env

# Tile shape for the score kernel, overridable for tuning sweeps
# (ops/_tiles.py).  Read once at import: the values are
# jit-static, so changing them mid-process would silently recompile
# rather than retune.
_TILE_P = tile_env("BLANCE_FUSED_TILE_P", 256, 8)
_TILE_N = tile_env("BLANCE_FUSED_TILE_N", 2048, 128)

__all__ = ["fused_score_min2", "ScoreInputs", "pack_score_inputs",
           "score_at_columns", "jitter_hash"]

_INF = 1.0e9
_RULE_MISS = 1.0e6
_RULE_TIER = 1.0e4
_J_MUL_P = 2654435761 - (1 << 32)  # int32 two's-complement bits of the
_J_MUL_N = 40503                   # unsigned Weyl multiplier 2654435761


def jitter_hash(pi: jnp.ndarray, ni: jnp.ndarray) -> jnp.ndarray:
    """THE deterministic tie-break hash, in [0, 1): Weyl-style over
    GLOBAL (partition, node) indices.  One spelling shared by the fused
    kernel, the point evaluator, the matrix engine in plan/tensor.py,
    and the test oracle — cross-engine decision equivalence depends on
    these being identical.  Inputs must be int32: XLA/Mosaic integer
    ops wrap two's-complement, so the masked low 16 bits equal the
    unsigned sequence bit-for-bit, and int32->float32 is a cast Mosaic
    can lower in-kernel (uint32->float32 is not)."""
    return ((pi * jnp.int32(_J_MUL_P) + ni * jnp.int32(_J_MUL_N))
            & jnp.int32(0xFFFF)).astype(jnp.float32) / 65536.0


class ScoreInputs(NamedTuple):
    """Packed per-slot score inputs (a pytree of arrays).

    [N_l]-shaped (this shard's columns):
      base       f32 — fill factor / node weight (the balance term)
      neg_boost  f32 — -min(node_weight, 0)
      validf     f32 — 1.0 valid / 0.0 removed
      cand_g     [2*nrules (or 1), N_l] i32 — per rule: candidates'
                 include-level gids, then exclude-level gids
    [P]-shaped:
      stick      f32 — stickiness[:, si]
      prev_slot  i32 — prev[:, si, ri] (-1 none): same-ordinal bonus
      prev_state [P, R] i32 — prev[:, si, :]: sticky-holder bonus
      taken      [P, T] i32 — exclusivity id columns (-1 padded)
      present    [P, A] f32 — 1.0 where the rule anchor exists
      a_inc_g / a_exc_g [P, A*nrules (or 1)] i32 — anchors' gids per
                 rule level, -3 where the anchor's gid is invalid
                 (matches nothing; candidate gids are >= 0)
      any_anchor f32 — 1.0 where any anchor present (penalty gate)
    Node ids in prev_slot / prev_state / taken are GLOBAL (compared
    against global column ids in-kernel)."""

    base: jnp.ndarray
    neg_boost: jnp.ndarray
    validf: jnp.ndarray
    cand_g: jnp.ndarray
    stick: jnp.ndarray
    prev_slot: jnp.ndarray
    prev_state: jnp.ndarray
    taken: jnp.ndarray
    present: jnp.ndarray
    a_inc_g: jnp.ndarray
    a_exc_g: jnp.ndarray
    any_anchor: jnp.ndarray


def pack_score_inputs(
    *,
    total_l, total_p, w_div_l, neg_boost_l, valid_l,
    stickiness_si, prev_slot, prev_state, taken_ids,
    anchors, gids_l, gid_valid, gids, rules,
) -> ScoreInputs:
    """Build ScoreInputs from run_auction's existing terms.

    ``gids_l`` holds this shard's candidate columns; anchor lookups use
    the full ``gids``/``gid_valid`` tables (global ids), exactly like
    _hier_penalty."""
    base = (0.001 * total_l / jnp.maximum(total_p, 1.0)) / w_div_l
    validf = valid_l.astype(jnp.float32)
    p = prev_slot.shape[0]
    nrules = len(rules)
    if nrules:
        cand_g = jnp.concatenate(
            [jnp.stack([gids_l[inc] for (inc, _exc) in rules]),
             jnp.stack([gids_l[exc] for (_inc, exc) in rules])], axis=0)
        a_width = anchors.shape[1]
        aa = jnp.maximum(anchors, 0)
        inc_cols = []
        exc_cols = []
        for ai in range(a_width):
            for (inc, exc) in rules:
                inc_cols.append(jnp.where(
                    gid_valid[inc][aa[:, ai]], gids[inc][aa[:, ai]], -3))
                exc_cols.append(jnp.where(
                    gid_valid[exc][aa[:, ai]], gids[exc][aa[:, ai]], -3))
        a_inc_g = jnp.stack(inc_cols, axis=1)
        a_exc_g = jnp.stack(exc_cols, axis=1)
        present = (anchors >= 0).astype(jnp.float32)
        any_anchor = jnp.any(anchors >= 0, axis=1).astype(jnp.float32)
    else:
        cand_g = jnp.zeros((1, base.shape[0]), jnp.int32)
        a_inc_g = jnp.full((p, 1), -3, jnp.int32)
        a_exc_g = jnp.full((p, 1), -3, jnp.int32)
        present = jnp.zeros((p, 1), jnp.float32)
        any_anchor = jnp.zeros(p, jnp.float32)
    if taken_ids:
        taken = jnp.stack(taken_ids, axis=1)
    else:
        taken = jnp.full((p, 1), -1, jnp.int32)
    return ScoreInputs(
        base=base, neg_boost=neg_boost_l, validf=validf, cand_g=cand_g,
        stick=stickiness_si, prev_slot=prev_slot, prev_state=prev_state,
        taken=taken, present=present, a_inc_g=a_inc_g, a_exc_g=a_exc_g,
        any_anchor=any_anchor)


def _kernel(price_ref, base_ref, nb_ref, validf_ref, cand_ref, stick_ref,
            pslot_ref, pstate_ref, taken_ref, present_ref, ainc_ref,
            aexc_ref, anyr_ref, pbase_ref, noff_ref,
            best_ref, idx_ref, second_ref, raw_ref, *,
            tile_p: int, tile_n: int, n: int, nrules: int, a_width: int,
            r_width: int, t_width: int, jitter_scale: float):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[:] = jnp.full_like(best_ref, float("inf"))
        second_ref[:] = jnp.full_like(second_ref, float("inf"))
        idx_ref[:] = jnp.zeros_like(idx_ref)
        raw_ref[:] = jnp.zeros_like(raw_ref)

    tp = stick_ref.shape[0]
    tn = price_ref.shape[1]
    cols_local = jax.lax.broadcasted_iota(jnp.int32, (tp, tn), 1) + \
        j * tile_n
    cols_g = cols_local + noff_ref[0, 0]  # GLOBAL ids for id compares

    # --- the score formula, term order mirroring run_auction ---
    base = base_ref[:]
    nb = nb_ref[:]
    stick = stick_ref[:]  # [tp, 1]
    score = base + jnp.where(nb > 0, jnp.maximum(nb, stick), 0.0)
    score = score - 0.01 * (pslot_ref[:] == cols_g).astype(jnp.float32)
    pstate = pstate_ref[:]
    sticky = pstate[:, 0:1] == cols_g
    for r in range(1, r_width):
        sticky = sticky | (pstate[:, r:r + 1] == cols_g)
    score = score - stick * sticky.astype(jnp.float32)
    if nrules:
        cand = cand_ref[:]
        ainc = ainc_ref[:]
        aexc = aexc_ref[:]
        present = present_ref[:]
        pen = jnp.full(score.shape, _RULE_MISS, jnp.float32)
        for idx in range(nrules):
            sat = jnp.ones(score.shape, jnp.bool_)
            for ai in range(a_width):
                col = ai * nrules + idx
                inc_same = ainc[:, col:col + 1] == cand[idx:idx + 1, :]
                exc_same = aexc[:, col:col + 1] == \
                    cand[nrules + idx:nrules + idx + 1, :]
                # (absent anchor passes) OR (rule gate) — spelled as
                # boolean algebra, not jnp.where: a select over i1
                # vectors lowers to an i8->i1 truncation Mosaic rejects.
                sat = sat & ((present[:, ai:ai + 1] <= 0.0)
                             | (inc_same & ~exc_same))
            pen = jnp.where(sat, jnp.minimum(pen, idx * _RULE_TIER), pen)
        score = score + jnp.where(anyr_ref[:] > 0, pen, 0.0)
    taken = taken_ref[:]
    tk = taken[:, 0:1] == cols_g
    for t in range(1, t_width):
        tk = tk | (taken[:, t:t + 1] == cols_g)
    score = score + _INF * (tk | (validf_ref[:] == 0.0)).astype(jnp.float32)
    # Deterministic tie-break jitter — identical hash to _assign_slot's.
    pi = (pbase_ref[0, 0] + i * tile_p
          + jax.lax.broadcasted_iota(jnp.int32, score.shape, 0))
    score = score + jitter_scale * jitter_hash(pi, cols_g)
    # --- fused min2/argmin over score + price ---
    price = price_ref[:]
    x = score + price
    if n % tn:
        x = jnp.where(cols_local < n, x, float("inf"))

    tile_best = jnp.min(x, axis=1, keepdims=True)
    is_min = x == tile_best
    tile_idx = jnp.min(jnp.where(is_min, cols_local, n), axis=1,
                       keepdims=True)
    x_wo = jnp.where(cols_local == tile_idx, float("inf"), x)
    tile_second = jnp.min(x_wo, axis=1, keepdims=True)
    # Unpriced score at the tile argmin: best minus the price there.
    price_at = jnp.sum(
        jnp.where(cols_local == tile_idx, jnp.broadcast_to(price, x.shape),
                  0.0), axis=1, keepdims=True)
    tile_raw = tile_best - price_at

    run_best = best_ref[:]
    run_second = second_ref[:]
    new_second = jnp.minimum(jnp.maximum(run_best, tile_best),
                             jnp.minimum(run_second, tile_second))
    win = tile_best < run_best
    best_ref[:] = jnp.minimum(run_best, tile_best)
    second_ref[:] = new_second
    idx_ref[:] = jnp.where(win, tile_idx, idx_ref[:])
    raw_ref[:] = jnp.where(win, tile_raw, raw_ref[:])


@functools.partial(
    jax.jit, static_argnames=("nrules", "jitter_scale", "tile_p", "tile_n",
                              "interpret", "vma"))
def fused_score_min2(
    price: jnp.ndarray,  # [N_l] f32, +INF where closed
    si: ScoreInputs,
    pbase,  # [1, 1] i32: global partition index of local row 0 (jitter)
    noff,  # [1, 1] i32: global column offset of this shard
    *,
    nrules: int,
    jitter_scale: float,
    tile_p: int = _TILE_P,
    tile_n: int = _TILE_N,
    interpret: bool = False,
    vma: tuple = (),
):
    """(best, choice_LOCAL, second, raw) per row; score built in-VMEM.

    The caller adds ``noff`` to the returned choice for global ids.
    ``vma`` names the mesh axes the outputs vary over when called under
    shard_map (the partition axis always; the node axis too on a 2-D
    mesh) — shard_map's varying-axes checker requires the annotation on
    pallas_call outputs."""
    p = si.stick.shape[0]
    n = price.shape[0]
    if n == 0:
        raise ValueError("fused_score_min2 requires N >= 1")
    tp = min(tile_p, max(p, 1))
    tn = min(tile_n, n)
    grid = (pl.cdiv(p, tp), pl.cdiv(n, tn))

    r_width = si.prev_state.shape[1]
    t_width = si.taken.shape[1]
    a_width = si.present.shape[1]

    vma_set = frozenset(vma)
    out_shape = [
        jax.ShapeDtypeStruct((p, 1), jnp.float32, vma=vma_set),  # best
        jax.ShapeDtypeStruct((p, 1), jnp.int32, vma=vma_set),  # idx (local)
        jax.ShapeDtypeStruct((p, 1), jnp.float32, vma=vma_set),  # second
        jax.ShapeDtypeStruct((p, 1), jnp.float32, vma=vma_set),  # raw at idx
    ]
    out_spec = pl.BlockSpec((tp, 1), lambda i, j: (i, 0))
    row1 = pl.BlockSpec((1, tn), lambda i, j: (0, j))
    colp = lambda cols_: pl.BlockSpec((tp, cols_), lambda i, j: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0))

    best, idx, second, raw = pl.pallas_call(
        functools.partial(
            _kernel, tile_p=tp, tile_n=tn, n=n, nrules=nrules,
            a_width=a_width, r_width=r_width, t_width=t_width,
            jitter_scale=jitter_scale),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            row1,               # price
            row1,               # base
            row1,               # neg_boost
            row1,               # validf
            pl.BlockSpec((si.cand_g.shape[0], tn),
                         lambda i, j: (0, j)),  # cand_g
            colp(1),            # stick
            colp(1),            # prev_slot
            colp(r_width),      # prev_state
            colp(t_width),      # taken
            colp(a_width),      # present
            colp(si.a_inc_g.shape[1]),  # a_inc_g
            colp(si.a_exc_g.shape[1]),  # a_exc_g
            colp(1),            # any_anchor
            scalar,             # pbase
            scalar,             # noff
        ],
        out_specs=[out_spec, out_spec, out_spec, out_spec],
        interpret=interpret,
    )(
        price.reshape(1, n),
        si.base.reshape(1, n),
        si.neg_boost.reshape(1, n),
        si.validf.reshape(1, n),
        si.cand_g,
        si.stick.reshape(p, 1),
        si.prev_slot.reshape(p, 1),
        si.prev_state,
        si.taken,
        si.present,
        si.a_inc_g,
        si.a_exc_g,
        si.any_anchor.reshape(p, 1),
        jnp.asarray(pbase, jnp.int32).reshape(1, 1),
        jnp.asarray(noff, jnp.int32).reshape(1, 1),
    )
    return best[:, 0], idx[:, 0], second[:, 0], raw[:, 0]


def score_at_columns(
    rows: jnp.ndarray,  # [K] local row ids
    cols_global: jnp.ndarray,  # [K] GLOBAL column ids (>= 0)
    *,
    base_full: jnp.ndarray,  # [N] FULL node-replicated base
    neg_boost_full: jnp.ndarray,
    valid_full: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    anchors: Optional[jnp.ndarray],
    rules: tuple,
    prev_slot: jnp.ndarray,  # [P] global ids
    prev_state: jnp.ndarray,  # [P, R]
    taken_ids: tuple,
    stick: jnp.ndarray,  # [P]
    jitter_scale: float,
    pbase,  # [1, 1]
) -> jnp.ndarray:
    """The same score formula evaluated at single (row, col) pairs with
    [K] ops — phase B's waterfall probe when no matrix exists.  Inputs
    are the FULL node-replicated tables, so no node-axis collective is
    needed (every shard computes identically)."""
    from ..plan.tensor import _hier_tier_at  # shared rule semantics

    c = cols_global
    s = base_full[c]
    nb = neg_boost_full[c]
    stick_r = stick[rows]
    s = s + jnp.where(nb > 0, jnp.maximum(nb, stick_r), 0.0)
    s = s - 0.01 * (prev_slot[rows] == c).astype(jnp.float32)
    sticky = jnp.zeros(rows.shape[0], jnp.bool_)
    for r in range(prev_state.shape[1]):
        sticky = sticky | (prev_state[rows, r] == c)
    s = s - stick_r * sticky.astype(jnp.float32)
    if rules:
        s = s + _hier_tier_at(anchors[rows], c, gids, gid_valid, rules)
    tk = jnp.zeros(rows.shape[0], jnp.bool_)
    for tid in taken_ids:
        tk = tk | (tid[rows] == c)
    s = s + _INF * (tk | ~valid_full[c]).astype(jnp.float32)
    pi = (jnp.asarray(pbase).reshape(()) + rows).astype(jnp.int32)
    return s + jitter_scale * jitter_hash(pi, c.astype(jnp.int32))
