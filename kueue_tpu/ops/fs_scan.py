"""Device fair-sharing admission: the tournament + admit loop as ONE scan.

Round 3 left fair-sharing cycles permanently classify-only: the batched
``TournamentDRS`` computed per-round DRS values but the admission loop —
tournament winner selection, fit re-check, usage mutation, repeat —
stayed on the host because the within-cycle ordering is data-dependent on
DRS (verdict r3 item 3).  This module runs the WHOLE loop as one jitted
``lax.scan`` over rounds, so plain-admission fair-sharing cycles become
fully device-decided (FULL mode).

Reference semantics reproduced exactly (fair_sharing_iterator.go):

- Per round, the first remaining entry in heads order is taken; a
  parentless CQ's entry wins immediately (the iterator yields it), else
  the **tournament** runs over that entry's cohort tree: at every cohort
  node, the surviving candidate minimizes (DRS of its child-of-this-node
  ancestor with the entry's usage added, then priority desc, timestamp
  asc, then structural child order) — runTournament/entryComparer.less
  (:121,:167).
- DRS (fair_sharing.go:47-82): max over resources of borrowed-above-
  subtree-quota × 1000 // lendable-in-parent, then × 1000 // fairWeight;
  0 when not borrowing or at a root, MAX when weight is zero.  The
  int32-scaled tensors preserve the exact host values because every
  quantity of one resource shares the per-resource scale and
  floor((a/s)·1000/(b/s)) == floor(a·1000/b); the packer refuses shapes
  whose intermediate products could overflow int32 (host falls back).
- The winner is processed like the host admit loop: NO_FIT entries are
  discarded, fit entries re-check chain-local availability against the
  mutated usage (scheduler.go:372) and either admit (usage charged up
  the ancestor chain) or skip.

Decision parity is enforced against the host tournament path by
tests/test_fs_device.py and the fair-sharing conformance tables.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .quota_kernel import available_at, add_usage_chain

INF_I32 = np.int32(2**31 - 1)
MAX_DRS_I32 = np.int32(2**31 - 2)   # weight-zero sentinel (host MAX_DRS)


@dataclass
class FSStatics:
    """Fair-sharing structure tensors, cached per structure generation."""
    sq_mask: np.ndarray       # [N, F] bool: fr present in subtree_quota
    lendable_r: np.ndarray    # [N, R] int32 scaled lendable-in-parent
    onehot: np.ndarray        # [F, R] int32 fr -> resource
    node_level: np.ndarray    # [N] int32 (roots = 0)
    child_order: np.ndarray   # [N] int32 rank among parent's children
    n_levels: int
    drs_bound_base: int       # max scaled borrowing the statics allow


def build_fs_statics(snapshot, st) -> Optional[FSStatics]:
    """Build the static FS tensors for a PackedStructure.

    Returns None when the scaled DRS math could overflow int32 for ANY
    conceivable usage below the structure's quota ceilings — the
    scheduler then keeps the host tournament."""
    from .cycle import available_all_np
    N, F = st.subtree_quota.shape
    C = len(st.cq_names)
    R = len(st.resource_names)

    fr_to_r = np.zeros(F, dtype=np.int64)
    for fr, fi in st.fr_index.items():
        fr_to_r[fi] = st.r_index[fr.resource]
    onehot = (fr_to_r[:, None] == np.arange(R)[None, :]).astype(np.int32)

    # subtree-quota presence masks + child enumeration order from the
    # snapshot (cohorts before CQs, list order — _fs_tournament)
    from .packing import _iter_nodes
    cq_names, cohorts = _iter_nodes(snapshot)
    if list(cq_names) != list(st.cq_names):
        return None
    nodes = [snapshot.cluster_queues[n] for n in cq_names] + cohorts
    sq_mask = np.zeros((N, F), dtype=bool)
    for ni, node in enumerate(nodes):
        for fr in node.resource_node.subtree_quota:
            fi = st.fr_index.get(fr)
            if fi is not None:
                sq_mask[ni, fi] = True

    node_index: dict[int, int] = {id(n): i for i, n in enumerate(nodes)}
    child_order = np.zeros(N, dtype=np.int32)
    for ci, cohort in enumerate(cohorts):
        rank = 0
        for ch in cohort.child_cohorts:
            i = node_index.get(id(ch))
            if i is not None:
                child_order[i] = rank
                rank += 1
        for cq in cohort.child_cqs:
            i = node_index.get(id(cq))
            if i is not None:
                child_order[i] = rank
                rank += 1

    node_level = np.zeros(N, dtype=np.int32)
    for ni in range(N):
        lvl, p = 0, int(st.parent[ni])
        while p >= 0:
            lvl += 1
            p = int(st.parent[p])
        node_level[ni] = lvl
    n_levels = int(node_level.max()) + 1

    # lendable: potentialAvailable of the parent, masked to the frs of
    # the root's subtree quota, summed per resource (fair_sharing.go:86)
    potential = available_all_np(
        np.zeros((N, F), np.int64), st.subtree_quota, st.guaranteed,
        st.borrow_cap, st.has_borrow_limit, st.parent, st.depth)
    root_of = np.arange(N)
    for ni in range(N):
        cur = ni
        while st.parent[cur] >= 0:
            cur = int(st.parent[cur])
        root_of[ni] = cur
    p_safe = np.maximum(st.parent, 0)
    masked = np.where(sq_mask[root_of] & (st.parent >= 0)[:, None],
                      potential[p_safe], 0)
    lendable64 = masked @ onehot.astype(np.int64)                # [N, R]
    if lendable64.max(initial=0) > INF_I32:
        return None
    lendable_r = lendable64.astype(np.int32)

    # overflow ceiling: the largest borrowing any usage below the quota
    # plane could show is bounded by the total subtree quota (borrowing
    # never exceeds what parents can lend)
    drs_bound_base = int(np.abs(st.subtree_quota.astype(np.int64)).sum())
    return FSStatics(sq_mask=sq_mask, lendable_r=lendable_r,
                     onehot=onehot, node_level=node_level,
                     child_order=child_order, n_levels=n_levels,
                     drs_bound_base=drs_bound_base)


def fs_bounds_ok(statics: FSStatics, usage0, u_e) -> bool:
    """True when every intermediate DRS product stays inside int32.

    Structural bound: the device path only ever adds FIT-checked entry
    usage to usage from admitted workloads, so borrowing never exceeds
    the parent's lendable capacity and ratio <= 1000; the per-mille
    ratio is long division (``permille``), whose products stay under
    10 x lendable, and ratio*1000 is <= 10^6.  The kernel clamps the
    borrowing to the lendable so a violated assumption can't wrap."""
    b = (int(np.abs(usage0.astype(np.int64)).max(initial=0))
         + int(np.abs(u_e.astype(np.int64)).sum(axis=0).max(initial=0)))
    return window_bounds_ok(statics) and b < 2**31


def window_bounds_ok(statics: FSStatics) -> bool:
    """The lendable quota's part of ``fs_bounds_ok``: what the fused
    window's tournament needs of a structure."""
    lend_max = int(statics.lendable_r.astype(np.int64).max(initial=0))
    return lend_max * 10 < 2**31


def permille(b, lend):
    """``b * 1000 // lend`` for ``0 <= b <= lend``, exact in int32 while
    ``10 * lend`` is: three digits of long division, so that a lendable
    quota in milli-cpu (10^8 at the cells' size) does not overflow the
    product the division would otherwise need."""
    rem = b
    out = jnp.zeros_like(b)
    for _ in range(3):
        rem = rem * 10
        digit = rem // lend
        rem = rem - digit * lend
        out = out * 10 + digit
    return out


def entry_paths(cq_safe, parent, depth: int):
    """[W, depth] the nodes from each entry's CQ up to its root (-1
    past the root)."""
    paths = [cq_safe]
    for _ in range(depth - 1):
        prev = paths[-1]
        nxt = jnp.where(prev >= 0, parent[jnp.maximum(prev, 0)], -1)
        paths.append(jnp.where(paths[-1] >= 0, nxt, -1))
    return jnp.stack(paths, axis=1)


def tournament(usage, remaining, path, cq_safe, u_e, prio, ts_rank,
               subtree, sq_mask, guaranteed, parent, node_level, weights,
               lendable_r, onehot, child_order, *, n_levels: int):
    """One round of fair_sharing_iterator.go's tournament over every
    cohort tree at once: ``node_winner [N]``, the remaining entry each
    node's subtree yields (-1 where none remains).  A root's entry is
    its tree's pick; a parentless CQ is its own root.

    ``usage [N, F]`` is the round's node usage, ``remaining [W]`` the
    entries still in the cycle, ``path [W, L]`` each entry's nodes from
    its CQ up (``entry_paths``), ``u_e [W, F]`` the usage each entry
    would add, ``prio`` and ``ts_rank`` its priority and an
    equality-preserving rank of its queue-order timestamp.  At every
    node the survivor minimizes the DRS of its child-of-this-node
    ancestor with the entry's usage added, then priority descending,
    timestamp ascending, then child order (runTournament /
    entryComparer.less, :121, :167).  ``fs_admit_scan`` takes the pick
    of the first remaining entry's tree a round; the fused window
    (ops/burst.py) takes every tree's pick a round."""
    N = usage.shape[0]
    W, L = path.shape
    cidx = jnp.arange(W, dtype=jnp.int32)

    # -- DRS of every remaining entry at every path level -------------
    drs_lv = []
    carry_u = u_e                                      # [W, F]
    for lvl in range(L):
        node = path[:, lvl]
        alive = node >= 0
        ns = jnp.maximum(node, 0)
        has_par = alive & (parent[ns] >= 0)
        u_after = usage[ns] + carry_u
        borrowed = jnp.maximum(0, u_after - subtree[ns]) * sq_mask[ns]
        borrowing_r = borrowed @ onehot                # [W, R]
        has_borrow = jnp.any(borrowing_r > 0, axis=1)
        lend = lendable_r[ns]
        qual = (borrowing_r > 0) & (lend > 0)
        # borrowing <= lendable in every reachable state (fit-checked
        # additions over admitted usage); the clamp guards the int32
        # product if that invariant is ever violated
        safe_b = jnp.minimum(borrowing_r, jnp.maximum(lend, 1))
        ratio = jnp.where(qual, permille(safe_b, jnp.maximum(lend, 1)),
                          -1)
        drs_raw = jnp.max(ratio, axis=1)
        w = weights[ns]
        core = drs_raw * 1000 // jnp.maximum(w, 1)
        dws = jnp.where(has_borrow, core, 0)
        dws = jnp.where(w == 0, MAX_DRS_I32, dws)
        dws = jnp.where(has_par, dws, 0)
        drs_lv.append(dws)
        local_avail = jnp.maximum(0, guaranteed[ns] - usage[ns])
        carry_u = jnp.where(alive[:, None],
                            jnp.maximum(0, carry_u - local_avail),
                            carry_u)
    drs = jnp.stack(drs_lv, axis=1)                    # [W, L]

    # -- bottom-up winner propagation ---------------------------------
    # node_winner[n] = index of the best remaining entry in n's
    # subtree; promoted level by level with 4-key scatter-argmin
    # (drs at the child node, priority desc, ts asc, child order).
    # Only live entries scatter; padded/consumed rows target the
    # out-of-bounds drop bucket (each CQ holds at most one head)
    tgt0 = jnp.where(remaining, cq_safe, N)
    node_winner = jnp.full(N, -1, dtype=jnp.int32).at[tgt0].set(
        cidx, mode="drop")
    cq_lv = node_level[cq_safe]                        # [W]
    for lvl in range(n_levels - 1, 0, -1):
        # promote winners of level-`lvl` nodes into their parents
        is_l = node_level == lvl
        src = jnp.arange(N)
        has_w = is_l & (node_winner >= 0) & (parent >= 0)
        e = jnp.maximum(node_winner, 0)
        # the winner's drs AT the child node: path index = depth of
        # the entry's CQ minus the node's level
        li = jnp.clip(cq_lv[e] - lvl, 0, L - 1)
        k_drs = jnp.where(has_w, drs[e, li], INF_I32)
        k_prio = jnp.where(has_w, -prio[e], INF_I32)
        k_ts = jnp.where(has_w, ts_rank[e], INF_I32)
        k_ord = jnp.where(has_w, child_order[src], INF_I32)
        p_s = jnp.maximum(parent, 0)
        tgt = jnp.where(has_w, p_s, N)                 # drop bucket N
        m1 = jnp.full(N + 1, INF_I32, jnp.int32).at[tgt].min(k_drs)
        ok1 = has_w & (k_drs == m1[tgt])
        m2 = jnp.full(N + 1, INF_I32, jnp.int32).at[tgt].min(
            jnp.where(ok1, k_prio, INF_I32))
        ok2 = ok1 & (k_prio == m2[tgt])
        m3 = jnp.full(N + 1, INF_I32, jnp.int32).at[tgt].min(
            jnp.where(ok2, k_ts, INF_I32))
        ok3 = ok2 & (k_ts == m3[tgt])
        m4 = jnp.full(N + 1, INF_I32, jnp.int32).at[tgt].min(
            jnp.where(ok3, k_ord, INF_I32))
        ok4 = ok3 & (k_ord == m4[tgt])
        promoted = jnp.full(N + 1, -1, jnp.int32).at[tgt].max(
            jnp.where(ok4, node_winner, -1))
        node_winner = jnp.where(
            (node_level == lvl - 1) & (promoted[:N] >= 0),
            promoted[:N], node_winner)
    return node_winner


@partial(jax.jit, static_argnames=("depth", "n_levels"))
def fs_admit_scan(usage0, subtree, sq_mask, guaranteed, borrow_cap,
                  has_blim, parent, node_level, weights, lendable_r,
                  onehot, child_order,
                  wl_cq, u_e, nofit, prio, ts_rank, valid,
                  *, depth: int, n_levels: int):
    """The fair-sharing cycle as one scan: W rounds of tournament +
    admit.  Returns (order [W] winner per round or -1, admitted [W],
    processed [W]) in head order; a fit head with ``processed`` and not
    ``admitted`` lost capacity in-cycle (skip)."""
    W = wl_cq.shape[0]
    L = depth
    cq_safe = jnp.maximum(wl_cq, 0)
    # static per entry: the path from its CQ to the root
    path = entry_paths(cq_safe, parent, L)             # [W, L]
    parentless = parent[cq_safe] < 0

    def round_step(carry, _):
        usage, remaining = carry
        live = remaining & valid
        node_winner = tournament(
            usage, live, path, cq_safe, u_e, prio, ts_rank, subtree,
            sq_mask, guaranteed, parent, node_level, weights, lendable_r,
            onehot, child_order, n_levels=n_levels)
        # the first remaining entry in heads order names the tree
        any_remaining = jnp.any(live)
        e0 = jnp.argmax(live).astype(jnp.int32)
        root = cq_safe[e0]
        for _ in range(L - 1):
            p = parent[root]
            root = jnp.where(p >= 0, jnp.maximum(p, 0), root)
        tw = node_winner[root]
        winner = jnp.where(parentless[e0] | (tw < 0), e0, tw)
        winner = jnp.where(any_remaining, winner, -1)

        # -- process the winner (host admit-loop semantics) -----------
        ws = jnp.maximum(winner, 0)
        is_live = winner >= 0
        w_cq = cq_safe[ws]
        avail = available_at(usage, subtree, guaranteed, borrow_cap,
                             has_blim, parent, w_cq, depth)
        w_u = u_e[ws]                                  # [F]
        rel = w_u > 0
        fits = jnp.all(jnp.where(rel, w_u <= avail, True))
        can_admit = is_live & ~nofit[ws] & fits
        delta = jnp.where(can_admit, w_u, 0)
        usage = add_usage_chain(usage, jnp.where(can_admit, w_cq, -1),
                                delta, guaranteed, parent, depth)
        remaining = remaining.at[ws].set(
            jnp.where(is_live, False, remaining[ws]))
        return (usage, remaining), (winner, can_admit)

    remaining0 = valid
    (_, _), (order, admit_o) = jax.lax.scan(
        round_step, (usage0, remaining0), None, length=W)
    z = jnp.zeros(W, dtype=bool)
    sel = jnp.maximum(order, 0)
    live = order >= 0
    admitted = z.at[sel].max(admit_o & live)
    processed = z.at[sel].max(live)
    return order, admitted, processed
