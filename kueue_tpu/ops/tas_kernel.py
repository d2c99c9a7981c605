"""Topology-aware scheduling on device (SURVEY §7 stage 7).

The domain tree (block → rack → host) is the flavor's ``TASLayout``
(``cache/tas_cache.py``): the domains of a level sorted by id tuple, so
the children of one domain are contiguous and index order is id order
(the reference's tie-break).  The reference's two-phase algorithm
(tas_flavor_snapshot.go:406-613) answers many placement questions in
one launch (``place_queries``):

- phase 1 ``level_states``: a leaf holds the fewest pods over the
  requested resources (free // per_pod), a domain the sum of its
  children, from prefix sums over the contiguous children;
- phase 2: the best domain at the requested level (BestFit: the fewest
  pods that still hold the question), the climb of a preferred level,
  else the split over the roots; then the descent inside the picked
  domains, a level at a time.

``answer_queries`` lays questions out and answers them; the cycle's
topology stage (``CycleSolver.tas_stage``) and the host walk's
``TASFlavorSnapshot.find_topology_assignment`` both ask through it.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .packing import _bucket, coarse_bucket

I32_MAX = 2**31 - 1

# ---------------------------------------------------------------------------
# The batched topology stage: a cycle's queries in one launch
# ---------------------------------------------------------------------------
#
# A launch answers U queries against one flavor's tree.  Each query names
# a *shape*: a per-pod request, the plane it is counted against (the
# leaves' free capacity, or their allocatable for a query against the
# empty tree) and the leaves the head's earlier PodSets were charged on.
# Phase 1 runs once a shape (the leaf plane is read once a launch, not
# once a query); phase 2 picks a domain a query from its shape's level
# states, and the descent works inside the picked domains only: their
# children are contiguous in the level-sorted layout, so a frontier of
# at most ``K`` picked domains (K >= the query's pods) expands to a
# ``[K, fanout]`` block a level.  What comes back is compact: ``K``
# (leaf, pods) pairs a query.

QUERY_REQUIRED, QUERY_PREFERRED, QUERY_UNCONSTRAINED = 0, 1, 2
# query-count rungs: every distinct launch shape is a compilation
TAS_Q_LADDER = (8, 64, 512, 4096)
TAS_K_MIN = 8
TAS_S_MIN = 4        # shapes a launch: a pod shape x (free, empty) x 2


def level_states(plane, pod, child_start):
    """Phase 1 for one shape (fillInCounts): the pods each domain holds,
    a tuple of [N_l] int32 top level first.  ``plane`` [N_leaf, R] is
    what the leaves have (non-negative), ``pod`` [R] the request a pod
    (0: not requested), ``child_start[l]`` [N_l + 1] the first child of
    each level-l domain.  Exact where no level's sum passes int32,
    which ``answer_queries`` checks before it asks."""
    need = pod > 0
    per = jnp.where(need[None, :], plane // jnp.maximum(pod, 1)[None, :],
                    I32_MAX)
    leaf = jnp.where(jnp.any(need), jnp.min(per, axis=1), 0)
    states = [leaf]
    for bounds in reversed(child_start):
        cs = jnp.concatenate([jnp.zeros(1, leaf.dtype),
                              jnp.cumsum(states[0])])
        states.insert(0, cs[bounds[1:]] - cs[bounds[:-1]])
    return tuple(states)


def _rank_order(state, ids, valid, profile: str):
    """Positions of a row in sortedDomains order: (-state, id), or for
    LeastFree the reverse (state, -id); invalid slots last."""
    big = jnp.int32(I32_MAX)
    if profile == "leastfree":
        return jnp.lexsort((-ids, jnp.where(valid, state, big)))
    return jnp.lexsort((ids, jnp.where(valid, -state, big)))


def _allocate_row(state, ids, valid, count, profile: str):
    """``_select_from`` over one row of sibling domains: ``count`` pods
    taken whole domain by whole domain in sortedDomains order until the
    rest fits in one; under BestFit the rest goes to the tightest
    (fewest pods, then lowest id) domain at or after that one that holds
    it.  Returns the pods a slot of the row."""
    order = _rank_order(state, ids, valid, profile)
    so = jnp.where(valid[order], state[order], 0)
    io = ids[order]
    exc = jnp.cumsum(so) - so
    rem = count - exc
    absorb = valid[order] & (so >= rem) & (rem > 0)
    has_j = jnp.any(absorb)
    j = jnp.argmax(absorb)
    rem_j = rem[j]
    pos = jnp.arange(so.shape[0])
    greedy = jnp.clip(rem, 0, so)
    if profile == "bestfit":
        cand = (pos >= j) & valid[order] & (so >= rem_j)
        min_s = jnp.min(jnp.where(cand, so, I32_MAX))
        tight = cand & (so == min_s)
        pick = jnp.argmin(jnp.where(tight, io, I32_MAX))
    else:
        pick = j
    last = jnp.where(pos == pick, rem_j, 0)
    take_o = jnp.where(has_j, jnp.where(pos < j, so, last), greedy)
    return jnp.zeros_like(take_o).at[order].set(take_o)


def _best_domain(state, count, profile: str):
    """``_find_fit_at``: the fitting domain of a level (BestFit and
    LeastFree: fewest pods; MostFree: most), lowest index on a tie;
    -1 where none fits."""
    fits = state >= count
    key = jnp.where(fits, -state if profile == "mostfree" else state,
                    I32_MAX)
    return jnp.where(jnp.any(fits), jnp.argmin(key), -1)


@partial(jax.jit, static_argnames=("level_sizes", "fanouts", "k",
                                   "profile", "uprofile"))
def place_queries(free, cap, child_start, sh_pod, sh_empty, sh_cleaf,
                  sh_camt, q_shape, q_count, q_kind, q_level, *,
                  level_sizes: tuple[int, ...], fanouts: tuple[int, ...],
                  k: int, profile: str, uprofile: str):
    """U queries in one launch.

    free, cap: [N_leaf, R] int32 (non-negative, scaled); child_start:
    tuple of [N_l + 1] int32, the first child of each level-l domain at
    level l + 1; sh_pod [S, R] int32 (0 = not requested), sh_empty [S]
    bool, sh_cleaf [S, C] int32 (-1 pad; a leaf once a shape) and
    sh_camt [S, C, R] int32: the charges of a shape's plane; q_*: [U]
    int32, the query's shape, pods, kind (QUERY_*) and level.
    ``fanouts[l]``: the most children a level-l domain has; ``k``: the
    frontier's width (>= every query's pods); ``profile`` /
    ``uprofile``: the sortedDomains profile of a placement, and of an
    unconstrained one's split over the roots.

    Returns (ok [U] bool, level [U] int32: the level whose domain was
    picked, -1 for a split over the roots, leaf [U, k] int32 (-1 pad),
    pods [U, k] int32, best [U] int32: the most pods one domain of a
    required level holds, or all the roots hold)."""
    L = len(level_sizes)

    # -- phase 1, once a shape -----------------------------------------
    def shape_states(pod, empty, cleaf, camt):
        plane = jnp.where(empty, cap, free)
        if cleaf.shape[0]:
            on = cleaf >= 0
            plane = plane.at[jnp.maximum(cleaf, 0)].add(
                -jnp.where(on[:, None], camt, 0))
        return level_states(jnp.maximum(plane, 0), pod, child_start)

    sh_states = jax.vmap(shape_states)(sh_pod, sh_empty, sh_cleaf, sh_camt)

    # -- phase 2 and the descent, a query at a time --------------------
    def one(shape, count, kind, level):
        st = [s[shape] for s in sh_states]
        best = [_best_domain(st[lvl], count, profile) for lvl in range(L)]
        best_a = jnp.stack(best)
        lvls = jnp.arange(L)
        # preferred: the lowest level from the requested one upward
        # whose best domain fits; required: the requested level only
        fits_at = best_a >= 0
        pref = jnp.max(jnp.where(fits_at & (lvls <= level), lvls, -1))
        req = jnp.where(fits_at[level], level, -1)
        chosen = jnp.where(kind == QUERY_REQUIRED, req,
                           jnp.where(kind == QUERY_PREFERRED, pref, -1))
        total = jnp.sum(st[0])
        split = chosen < 0
        ok = jnp.where(kind == QUERY_REQUIRED, chosen >= 0,
                       (chosen >= 0) | (total >= count))
        # the frontier: (domain, pods) at level ``at``
        n0 = level_sizes[0]
        ids0 = jnp.arange(n0, dtype=jnp.int32)
        roots_p = _allocate_row(st[0], ids0, jnp.ones(n0, bool), count,
                                profile)
        roots_u = _allocate_row(st[0], ids0, jnp.ones(n0, bool), count,
                                uprofile)
        roots = jnp.where(kind == QUERY_UNCONSTRAINED, roots_u, roots_p)
        r_dom, = jnp.nonzero(roots > 0, size=k, fill_value=-1)
        r_cnt = jnp.where(r_dom >= 0, roots[jnp.maximum(r_dom, 0)], 0)
        pick = best_a[jnp.maximum(chosen, 0)]
        p_dom = jnp.full(k, -1, jnp.int32).at[0].set(pick)
        p_cnt = jnp.zeros(k, jnp.int32).at[0].set(count)
        dom = jnp.where(split, r_dom, p_dom).astype(jnp.int32)
        cnt = jnp.where(split, r_cnt, p_cnt).astype(jnp.int32)
        at = jnp.where(split, 0, chosen)
        for lvl in range(L - 1):
            fan = fanouts[lvl]
            first = child_start[lvl][jnp.maximum(dom, 0)]
            end = child_start[lvl][jnp.maximum(dom, 0) + 1]
            kids = first[:, None] + jnp.arange(fan)[None, :]
            valid = (kids < end[:, None]) & (dom >= 0)[:, None]
            kids_c = jnp.minimum(kids, level_sizes[lvl + 1] - 1)
            take = jax.vmap(lambda s_, i_, v_, c_: _allocate_row(
                s_, i_, v_, c_, profile))(
                    st[lvl + 1][kids_c], kids_c, valid, cnt)
            flat = jnp.where(valid, take, 0).reshape(-1)
            sel, = jnp.nonzero(flat > 0, size=k, fill_value=-1)
            n_dom = jnp.where(sel >= 0,
                              kids_c.reshape(-1)[jnp.maximum(sel, 0)], -1)
            n_cnt = jnp.where(sel >= 0, flat[jnp.maximum(sel, 0)], 0)
            go = at <= lvl
            dom = jnp.where(go, n_dom, dom).astype(jnp.int32)
            cnt = jnp.where(go, n_cnt, cnt).astype(jnp.int32)
        ok = ok & (jnp.sum(cnt) == count)
        diag = jnp.where(kind == QUERY_REQUIRED,
                         jnp.stack([jnp.max(s) for s in st])[level], total)
        return (ok, jnp.where(split, -1, chosen).astype(jnp.int32),
                jnp.where(ok, dom, -1), jnp.where(ok, cnt, 0), diag)

    return jax.vmap(one)(q_shape, q_count, q_kind, q_level)


@dataclass
class TASQuery:
    """One PodSet's placement question against a flavor's tree."""
    per_pod: np.ndarray               # [R] int64 on the layout's resources
    count: int
    kind: int                         # QUERY_*
    level: int                        # the requested level (-1: none)
    empty: bool = False               # against the leaves' allocatable
    charges: tuple = ()               # ((leaf index, [R] int64), ...):
                                      # the head's earlier PodSets


@dataclass
class TASAnswer:
    ok: bool
    level: int                        # the picked domain's, -1: a split
    placement: list                   # [(leaf index, pods)] in leaf order
    best: int                         # for the failure message


def _statics(layout):
    """The layout's device-side constants, built once a layout."""
    st = getattr(layout, "_device_statics", None)
    if st is None:
        starts = [np.asarray(c, dtype=np.int32) for c in layout.child_start]
        fanouts = tuple(max(1, int(np.diff(c).max(initial=1)))
                        for c in starts)
        st = layout._device_statics = (tuple(jnp.asarray(c) for c in starts),
                                       fanouts)
    return st


def _distinct(queries: list, R: int) -> tuple:
    """The questions' shapes and the distinct questions among them.  A
    shape is (per-pod [R] int64, empty, charge leaves [C] int64, charges
    [C, R] int64), a leaf once; uncharged questions of one shape, pods,
    kind and level are one question.  Returns (shapes, uniq: (shape,
    count, kind, level) each, slot: the distinct question of each)."""
    shape_of: dict = {}
    shapes: list = []
    uniq_of: dict = {}
    uniq: list = []
    slot = []
    for q in queries:
        pod = np.asarray(q.per_pod, dtype=np.int64)
        if q.charges:
            leaves, inv = np.unique(
                np.fromiter((leaf for leaf, _ in q.charges), np.int64),
                return_inverse=True)
            amt = np.zeros((len(leaves), R), dtype=np.int64)
            np.add.at(amt, inv, np.stack([np.asarray(a, dtype=np.int64)
                                          for _, a in q.charges]))
            si = len(shapes)
            shapes.append((pod, bool(q.empty), leaves, amt))
            key = None
        else:
            skey = (pod.tobytes(), bool(q.empty))
            si = shape_of.get(skey)
            if si is None:
                si = shape_of[skey] = len(shapes)
                shapes.append((pod, bool(q.empty),
                               np.zeros(0, dtype=np.int64),
                               np.zeros((0, R), dtype=np.int64)))
            key = (si, int(q.count), int(q.kind), int(q.level))
        ui = uniq_of.get(key) if key is not None else None
        if ui is None:
            ui = len(uniq)
            uniq.append((si, int(q.count), int(q.kind), int(q.level)))
            if key is not None:
                uniq_of[key] = ui
        slot.append(ui)
    return shapes, uniq, slot


def _cap_stats(layout) -> tuple:
    """The allocatable's greatest common divisor, largest value and sum
    a resource, built once a layout.  The free plane is the allocatable
    less usage, so its sums are no larger."""
    st = getattr(layout, "_cap_stats", None)
    if st is None:
        cap = layout.capacity
        st = layout._cap_stats = (np.gcd.reduce(np.abs(cap), axis=0),
                                  cap.max(axis=0, initial=0),
                                  np.maximum(cap, 0).sum(axis=0))
    return st


def _exact(free: np.ndarray, layout, shapes: list) -> tuple:
    """The int32 kernel's scale and where it is exact.  Each resource is
    divided by the greatest common divisor of its values in the planes,
    the requests and the charges (``free // per_pod`` is unchanged by
    it: memory in bytes does not fit int32, in whole GiB it does).  A
    shape is exact where every resource it requests scales into int32
    in the planes and its request and charges, and where its pods summed
    over every leaf cannot pass int32 (so no level's state can).
    Returns (g [R] int64, exact [S] bool)."""
    g_cap, cap_top, total = _cap_stats(layout)
    g = np.gcd(g_cap, np.gcd.reduce(np.abs(free), axis=0))
    for pod, _, _, amt in shapes:
        g = np.gcd(g, np.gcd.reduce(np.abs(np.concatenate(
            [pod[None, :], amt])), axis=0))
    g = np.maximum(g, 1)
    bad = np.maximum(np.abs(free).max(axis=0, initial=0), cap_top) // g \
        > I32_MAX
    exact = np.ones(len(shapes), dtype=bool)
    for s, (pod, _, _, amt) in enumerate(shapes):
        need = pod > 0
        if not need.any():
            continue
        most = (total[need] // pod[need]).min()
        exact[s] = not (bad[need].any() or (pod // g > I32_MAX).any()
                        or (np.abs(amt) // g > I32_MAX).any()
                        or most > I32_MAX)
    return g, exact


def _plane32(plane: np.ndarray, g: np.ndarray) -> np.ndarray:
    """``plane // g``, negatives as 0; a column that does not fit int32
    is zeroed, and no exact shape requests it."""
    s = np.maximum(plane // g, 0)
    s[:, (s > I32_MAX).any(axis=0)] = 0
    return s.astype(np.int32)


def answer_queries(layout, free: np.ndarray, queries: list,
                   profile: str = "bestfit", uprofile: str = "bestfit",
                   stats: dict | None = None) -> list:
    """Every query against ``free`` (or the allocatable), in as few
    launches of ``place_queries`` as the rung ladder allows (one up to
    4,096 distinct queries).  Identical uncharged queries are answered
    once.  A query the int32 kernel cannot answer exactly (``_exact``)
    is answered None, for the caller to ask the host walk.  ``stats``
    gains ``tas_launches`` and ``tas_unique_queries``."""
    if not queries:
        return []
    R = layout.capacity.shape[1]
    shapes, uniq, slot = _distinct(queries, R)
    g, exact = _exact(free, layout, shapes)
    asked = [u for u, (si, _, _, _) in enumerate(uniq) if exact[si]]
    out: list = [None] * len(uniq)
    if asked:
        child_start, fanouts = _statics(layout)
        free32 = _plane32(free, g)
        cap32 = _plane32(layout.capacity, g)
        k = _bucket(max(uniq[u][1] for u in asked), minimum=TAS_K_MIN)
        top = TAS_Q_LADDER[-1]
        for lo in range(0, len(asked), top):
            part = asked[lo:lo + top]
            used = sorted({uniq[u][0] for u in part})
            local = {si: j for j, si in enumerate(used)}
            C = max(len(shapes[si][2]) for si in used)
            C = 0 if C == 0 else _bucket(C, minimum=1)
            U = coarse_bucket(len(part), TAS_Q_LADDER)
            S = _bucket(len(used), minimum=TAS_S_MIN)
            sh_pod = np.zeros((S, R), dtype=np.int32)
            sh_empty = np.zeros(S, dtype=bool)
            sh_cleaf = np.full((S, C), -1, dtype=np.int32)
            sh_camt = np.zeros((S, C, R), dtype=np.int32)
            for j, si in enumerate(used):
                pod, empty, leaves, amt = shapes[si]
                sh_pod[j] = pod // g
                sh_empty[j] = empty
                sh_cleaf[j, :len(leaves)] = leaves
                sh_camt[j, :len(leaves)] = amt // g
            q = np.zeros((4, U), dtype=np.int32)
            q[1] = 1
            q[2] = QUERY_UNCONSTRAINED
            for j, u in enumerate(part):
                si, count, kind, level = uniq[u]
                q[:, j] = (local[si], count, kind, max(level, 0))
            res = place_queries(
                free32, cap32, child_start, sh_pod, sh_empty, sh_cleaf,
                sh_camt, q[0], q[1], q[2], q[3],
                level_sizes=tuple(layout.level_sizes), fanouts=fanouts,
                k=k, profile=profile, uprofile=uprofile)
            ok, lvl, leaf, pods, best = jax.device_get(res)
            for j, u in enumerate(part):
                on = leaf[j] >= 0
                pl = sorted(zip(leaf[j][on].tolist(), pods[j][on].tolist()))
                out[u] = TASAnswer(bool(ok[j]), int(lvl[j]), pl,
                                   int(best[j]))
            if stats is not None:
                stats["tas_launches"] = stats.get("tas_launches", 0) + 1
    if stats is not None:
        stats["tas_unique_queries"] = (stats.get("tas_unique_queries", 0)
                                       + len(asked))
    return [out[u] for u in slot]


def warm_queries(layout, queries: list, profile: str = "bestfit",
                 uprofile: str = "bestfit") -> int:
    """Compile ``place_queries`` for every launch ``answer_queries`` can
    make of a subset of ``queries`` (the distinct questions a cycle can
    ask, against the free nodes and the empty tree): each query rung,
    shape bucket and frontier width up to theirs, with the charges they
    carry, and none.  Returns the launches built."""
    if not queries:
        return 0
    child_start, fanouts = _statics(layout)
    R = layout.capacity.shape[1]
    n_leaf = layout.capacity.shape[0]
    shapes, uniq, _ = _distinct(queries, R)
    C = max(len(s[2]) for s in shapes)
    widths = sorted({0, 0 if C == 0 else _bucket(C, minimum=1)})
    u_top = coarse_bucket(len(uniq), TAS_Q_LADDER)
    s_top = _bucket(len(shapes), minimum=TAS_S_MIN)
    k_top = _bucket(max(u[1] for u in uniq), minimum=TAS_K_MIN)
    plane = np.zeros((n_leaf, R), np.int32)
    built = 0
    for U in (u for u in TAS_Q_LADDER if u <= u_top):
        z = np.zeros(U, dtype=np.int32)
        S = TAS_S_MIN
        while S <= s_top:
            k = TAS_K_MIN
            while k <= k_top:
                for C in widths:
                    jax.device_get(place_queries(
                        plane, plane, child_start,
                        np.zeros((S, R), np.int32), np.zeros(S, bool),
                        np.full((S, C), -1, np.int32),
                        np.zeros((S, C, R), np.int32), z, z + 1,
                        z + QUERY_UNCONSTRAINED, z,
                        level_sizes=tuple(layout.level_sizes),
                        fanouts=fanouts, k=k, profile=profile,
                        uprofile=uprofile))
                    built += 1
                k *= 2
            S *= 2
    return built
