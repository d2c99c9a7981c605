"""Mesh construction and the sharded admission-cycle step.

``sharded_cycle_fn`` jits :func:`kueue_tpu.ops.cycle.solve_cycle` over a
2-D ``(wl, cq)`` mesh with explicit NamedShardings:

- workload tensors (``wl_*``) are sharded over ``wl`` — each chip
  classifies its slice of the pending batch against all flavors;
- quota-node tensors (``usage0``/``subtree``/…, first axis N) and the
  per-CQ flavor machinery (``nominal_cq``/``slot_fr``/…, first axis C) are
  sharded over ``cq`` — the quota plane is distributed and XLA all-gathers
  the slices a workload's CQ lookup needs.

The sequential admit scan (phase 2) carries the usage tensor; GSPMD keeps
it sharded over ``cq`` and reduces the per-step delta with ICI
collectives.  This is the multi-chip story for the north-star scale
(100k workloads × 1k CQs — BASELINE.json): wl for throughput, cq for a
quota plane too big for one chip's HBM.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.burst import _STATE_INPUTS as _STATE_NAMES
from ..ops.cycle import solve_cycle
from ..ops.packing import PackedCycle


def _require_devices(asked: int, have: int) -> None:
    """Asking for N shards and getting fewer is an error, never a
    quieter run: only the modelled chaos fault (BurstSolver.lose_devices)
    shrinks a mesh."""
    if asked > have:
        raise ValueError(
            f"{asked} shards requested but the default JAX backend has "
            f"{have} device(s) ({jax.devices()[0].platform})")


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 2-D (wl, cq) mesh over the first ``n_devices`` devices.

    ``n`` is factored as evenly as possible (8 → 4×2, 4 → 2×2, prime
    p → p×1) so both axes exist even on small meshes.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        _require_devices(n_devices, len(devices))
        devices = devices[:n_devices]
    n = len(devices)
    wl = n
    for cand in range(int(np.sqrt(n)), 0, -1):
        if n % cand == 0:
            wl = n // cand
            break
    cq = n // wl
    dev_array = np.asarray(devices).reshape(wl, cq)
    return Mesh(dev_array, axis_names=("wl", "cq"))


def cycle_args(packed: PackedCycle) -> tuple:
    """Positional args for solve_cycle, in signature order."""
    return (packed.usage0, packed.subtree_quota, packed.guaranteed,
            packed.borrow_cap, packed.has_borrow_limit, packed.parent,
            packed.nominal_cq, packed.slot_fr, packed.slot_valid,
            packed.cq_can_preempt_borrow, packed.wl_cq, packed.wl_requests,
            packed.wl_priority, packed.wl_timestamp)


def cycle_shardings(mesh: Mesh):
    """NamedShardings matching the cycle_args order."""
    node = NamedSharding(mesh, P("cq"))          # [N] / [N, F]
    cqax = NamedSharding(mesh, P("cq"))          # [C, ...]
    wl = NamedSharding(mesh, P("wl"))            # [W] / [W, R]
    rep = NamedSharding(mesh, P())
    return (node, node, node, node, node, rep,   # usage0..has_blim, parent
            cqax, cqax, cqax, cqax,              # nominal_cq..can_preempt
            wl, wl, wl, wl)                      # wl_cq..wl_timestamp


def sharded_cycle_fn(mesh: Mesh, depth: int, run_scan: bool = True):
    """A jitted solve_cycle bound to ``mesh`` with the standard shardings.

    Inputs whose sharded axis is not divisible by the mesh axis are left
    to GSPMD's uneven-sharding support; callers should still prefer
    bucket-padded shapes (the packer pads W) to keep layouts tight.
    """
    in_shardings = cycle_shardings(mesh)

    def step(*args):
        return solve_cycle(*args, depth=depth, run_scan=run_scan)

    return jax.jit(step, in_shardings=in_shardings)


# ---------------------------------------------------------------------------
# Production admit-scan sharding (CycleSolver.set_mesh routing)
# ---------------------------------------------------------------------------

def admit_scan_fns(mesh: Mesh, depth: int):
    """Factory for mesh-bound jitted variants of the production admit
    scans (ops.cycle.admit_scan{,_forests,_preempt}) with the standard
    shardings: quota plane over ``cq``, per-head tensors over ``wl``,
    the preemption-target universe replicated (targets are shared state
    every step may touch).  Returns {name: fn} with the same positional
    signatures as the unsharded kernels (statics bound per call via the
    ``forests``/``preempt`` wrappers)."""
    from ..ops.cycle import admit_scan, admit_scan_forests, admit_scan_preempt

    node = NamedSharding(mesh, P("cq"))
    rep = NamedSharding(mesh, P())
    wl = NamedSharding(mesh, P("wl"))
    # admit_scan(usage0, subtree, guaranteed, borrow_cap, has_blim,
    #            parent, nominal_cq, npb_cq, wl_cq, dec_fr, dec_amt,
    #            fit_mask, res_fr, res_amt, res_mask, res_borrows, order)
    base = (node, node, node, node, node, rep, node, node,
            wl, wl, wl, wl, wl, wl, wl, wl)

    flat = jax.jit(lambda *a: admit_scan(*a, depth=depth),
                   in_shardings=base + (wl,))

    forest_cache: dict = {}

    def forests(*args, forest_of_node, n_forests, max_forest_wl):
        key = (n_forests, max_forest_wl)
        fn = forest_cache.get(key)
        if fn is None:
            fn = jax.jit(
                lambda *a: admit_scan_forests(
                    *a, depth=depth, n_forests=n_forests,
                    max_forest_wl=max_forest_wl),
                in_shardings=base + (wl, rep))
            forest_cache[key] = fn
        return fn(*args, forest_of_node)

    preempt = jax.jit(
        lambda *a: admit_scan_preempt(*a, depth=depth),
        in_shardings=base + (wl, wl, wl, wl, rep, rep, wl))

    return {"flat": flat, "forest": forests, "preempt": preempt}


# ---------------------------------------------------------------------------
# Sharded fair-sharing tournament (CycleSolver.set_mesh routing)
# ---------------------------------------------------------------------------

def fs_scan_fn(mesh: Mesh, depth: int, n_levels: int):
    """A mesh-bound jitted fs_admit_scan with the standard shardings:
    quota-plane node tensors over ``cq``, per-head entry tensors over
    ``wl``, the tree-walk tables (parent/node_level/weights/child_order,
    gathered at every tournament level) replicated.  GSPMD partitions
    the SAME program the serial path jits — the W sequential rounds,
    the argmax winner selection, and every integer DRS division are
    unchanged — so decisions are bit-identical by construction."""
    from ..ops.fs_scan import fs_admit_scan

    node = NamedSharding(mesh, P("cq"))
    rep = NamedSharding(mesh, P())
    wl = NamedSharding(mesh, P("wl"))
    # fs_admit_scan(usage0, subtree, sq_mask, guaranteed, borrow_cap,
    #               has_blim, parent, node_level, weights, lendable_r,
    #               onehot, child_order, wl_cq, u_e, nofit, prio,
    #               ts_rank, valid)
    in_shardings = (node, node, node, node, node, node,
                    rep, rep, rep, node, rep, rep,
                    wl, wl, wl, wl, wl, wl)
    jf = jax.jit(
        lambda *a: fs_admit_scan(*a, depth=depth, n_levels=n_levels),
        in_shardings=in_shardings)
    n_cq = int(mesh.shape["cq"])
    n_wl = int(mesh.shape["wl"])

    def call(usage0, subtree, sq_mask, guaranteed, borrow_cap, has_blim,
             parent, node_level, weights, lendable_r, onehot,
             child_order, wl_cq, u_e, nofit, prio, ts_rank, valid):
        # GSPMD needs sharded dims divisible by their axis; pad nodes
        # to inert rows (parent -1, zero quota, never on any entry's
        # path) and heads to invalid rows (valid False, so they are
        # never `remaining` and the extra rounds yield winner -1),
        # then slice decisions back to the real head count
        N, W = usage0.shape[0], wl_cq.shape[0]
        Np = -(-N // n_cq) * n_cq
        Wp = -(-W // n_wl) * n_wl

        def pad(a, n, fill):
            return np.concatenate(
                [a, np.full((n - a.shape[0],) + a.shape[1:], fill,
                            a.dtype)]) if n != a.shape[0] else a

        args = (pad(usage0, Np, 0), pad(subtree, Np, 0),
                pad(sq_mask, Np, False), pad(guaranteed, Np, 0),
                pad(borrow_cap, Np, 0), pad(has_blim, Np, False),
                pad(parent, Np, -1), pad(node_level, Np, 0),
                pad(weights, Np, 1), pad(lendable_r, Np, 0),
                onehot, pad(child_order, Np, 0),
                pad(wl_cq, Wp, -1), pad(u_e, Wp, 0),
                pad(nofit, Wp, True), pad(prio, Wp, 0),
                pad(ts_rank, Wp, 0), pad(valid, Wp, False))
        order, admitted, processed = jf(*args)
        if Wp != W:
            # winners fill rounds 0..n_valid-1 (< W); the padded tail
            # is all -1, so the slice loses nothing
            order, admitted, processed = (
                order[:W], admitted[:W], processed[:W])
        return order, admitted, processed

    return call


# ---------------------------------------------------------------------------
# Sharded fused-burst dispatch (BurstSolver.set_shards routing)
# ---------------------------------------------------------------------------

def make_burst_mesh(n_devices: int) -> Mesh:
    """A 1-D ``("cq",)`` mesh over the first ``n_devices`` devices for
    the forest-partitioned burst kernel."""
    devices = jax.devices()
    _require_devices(n_devices, len(devices))
    return Mesh(np.asarray(devices[:n_devices]), axis_names=("cq",))


_I32_MAX = np.int32(2**31 - 1)

# pad fills per kernel input: a padded CQ row must never grow a head
# (wl_rank=INF), never hold quota, and never enter any forest's member
# or candidate tables — everything else about it is then inert
_C_FILLS = {
    "wl_req": 0, "wl_rank": _I32_MAX, "wl_cycle_rank": 0, "wl_prio": 0,
    "wl_uidrank": 0, "vec_ok": False, "wl_flavor_skip": 0,
    "elig0": False, "parked0": False, "resume0": 0, "adm0": False,
    "adm_seq0": 0, "adm_usage0": 0, "adm_uses0": False,
    "death0": _I32_MAX, "u_cq0": 0,
    "nominal_cq": 0, "npb_cq": 0, "slot_fr": -1, "slot_valid": False,
    "res_group": -1, "cq_can_preempt_borrow": False, "strict_cq": False,
    "cq_wcb_borrow": True, "cq_wcp_preempt": False,
    "wcq_lower": False, "rwc_enabled": False, "rwc_only_lower": False,
    "preempt_ok": False, "self_lmem": 0,
}
_N_FILLS = {
    "potential0": 0, "subtree": 0, "guaranteed": 0, "borrow_cap": 0,
    "has_blim": False,
}
_STATE_FILLS = (False, False, 0, False, 0, 0, False, _I32_MAX, 0)

# Residency tiers for the shard-resident boundary (BurstSolver keeps the
# permuted kernel inputs on the mesh between windows; only the tier that
# actually changed crosses the host→device boundary at a fresh pack):
#
# - STATIC:  pure functions of (structure generation, M, KC) — the
#   layout's value-remapped tables plus the quota plane and per-CQ
#   structure facts.  Permuted + uploaded once per layout lifetime.
# - SCATTER: per-record row facts.  The delta pack re-walks only
#   journal-dirty CQs and leaves every other record's rows in place
#   (ops/stream_pack.py), so for a chained delta pack these planes are
#   bit-identical outside the dirty rows — only those rows scatter.
# - GLOBAL:  globally recomputed each pack — dense cross-CQ ranks
#   (cycle/uid), the reservation-seq plane, and the modeling envelope
#   (preempt_ok depends on global scalars).  Always re-uploaded; all
#   are small relative to the row tier.
_ROW_STATIC = ("nominal_cq", "npb_cq", "slot_fr", "slot_valid", "res_group",
               "cq_can_preempt_borrow", "cq_wcb_borrow",
               "cq_wcp_preempt", "wcq_lower", "rwc_enabled",
               "rwc_only_lower", "self_lmem")
SCATTER_PLANES = ("wl_req", "wl_rank", "wl_prio", "vec_ok",
                  "wl_flavor_skip", "strict_cq",
                  "elig0", "parked0", "resume0", "adm0", "adm_usage0",
                  "adm_uses0", "death0", "u_cq0")
GLOBAL_PLANES = ("wl_cycle_rank", "wl_uidrank", "adm_seq0", "preempt_ok")


class BurstShardLayout:
    """Forest-partition of a burst plan across a 1-D ``cq`` mesh.

    Cohort forests are the fused kernel's independence boundary: every
    comparison it makes (heads argmin, candidate ordering, the
    entryOrdering sort, the admit scan's lanes) stays inside one forest,
    and all ordering keys are host-precomputed GLOBAL ranks carried by
    value — so partitioning whole forests onto shards, with the dirty
    reduction as a psum, reproduces the serial decisions bit-for-bit.

    The layout assigns forests to shards greedily onto the least-loaded
    shard — by CQ count, or by measured per-forest cycle cost when the
    solver has an EWMA from prior windows (``forest_cost``; assignment
    never affects decisions, every rank is carried by value).  It gives
    every shard equally padded
    local index spaces (Cs CQ slots, Gs forest rows, Ns = Cs + Hs quota
    nodes with CQ nodes first — the kernel's ``usage[:C]`` convention),
    and VALUE-REMAPS the member/candidate tables into local ids at
    identical slot positions, so ``tgt_words`` bit j still means global
    candidate slot j and the driver's apply path is untouched."""

    def __init__(self, plan, n_shards: int, forest_cost=None):
        a = plan.arrays
        st = plan.structure
        C, M, G, L, KC = plan.C, plan.M, plan.G, plan.L, plan.KC
        S = int(n_shards)
        self.n_shards = S
        self.M = M
        self._static_dev = None   # device-resident statics (solver tier)
        forest_of_cq = np.asarray(a["forest_of_cq"])
        parent = np.asarray(a["parent"])
        node_level = np.asarray(a["node_level"])
        members = np.asarray(a["members"])
        cand_rows = np.asarray(a["cand_rows"])
        cand_lmem = np.asarray(a["cand_lmem"])
        N = parent.shape[0]
        forest_of_node = np.asarray(st.forest_of_node)

        # greedy LPT: big forests first onto the least-loaded shard.
        # "Big" is CQ count by default; with a measured per-forest cycle
        # cost (EWMA of decided heads per window) the cost is the load,
        # with a small size term so never-fired forests still spread.
        counts = np.bincount(forest_of_cq, minlength=G)
        if forest_cost is not None and len(forest_cost) == G:
            weight = (np.asarray(forest_cost, dtype=np.float64)
                      + 1e-6 * counts)
            self.cost_balanced = True
        else:
            weight = counts.astype(np.float64)
            self.cost_balanced = False
        load = [0.0] * S
        forests_of: list[list[int]] = [[] for _ in range(S)]
        for g in sorted(range(G), key=lambda g: (-float(weight[g]), g)):
            s = min(range(S), key=lambda i: (load[i], i))
            forests_of[s].append(g)
            load[s] += float(weight[g])
        self.shard_cost = [round(x, 6) for x in load]
        mean_load = sum(load) / max(1, S)
        self.cost_ratio = (round(max(load) / mean_load, 4)
                           if mean_load > 0 else 1.0)
        for fl in forests_of:
            fl.sort()
        shard_of_forest = np.zeros(max(G, 1), dtype=np.int32)
        local_forest = np.zeros(max(G, 1), dtype=np.int32)
        for s, fl in enumerate(forests_of):
            for j, g in enumerate(fl):
                shard_of_forest[g] = s
                local_forest[g] = j

        cqs_of: list[list[int]] = [[] for _ in range(S)]
        for s, fl in enumerate(forests_of):
            for g in fl:
                for cq in members[g]:
                    if cq >= 0:
                        cqs_of[s].append(int(cq))
        cohorts_of: list[list[int]] = [[] for _ in range(S)]
        for nd in range(C, N):
            f = int(forest_of_node[nd])
            s = int(shard_of_forest[f]) if 0 <= f < G else 0
            cohorts_of[s].append(nd)

        Cs = max(1, max(len(x) for x in cqs_of))
        Gs = max(1, max(len(x) for x in forests_of))
        Hs = max(len(x) for x in cohorts_of)
        self.Cs, self.Gs, self.Ns = Cs, Gs, Cs + Hs
        Ns = self.Ns

        cq_perm = np.full((S, Cs), -1, dtype=np.int32)
        cq_pos = np.zeros(C, dtype=np.int64)
        local_cq = np.zeros(C, dtype=np.int32)
        for s, cqs in enumerate(cqs_of):
            for j, cq in enumerate(cqs):
                cq_perm[s, j] = cq
                cq_pos[cq] = s * Cs + j
                local_cq[cq] = j
        node_perm = np.full((S, Ns), -1, dtype=np.int32)
        node_perm[:, :Cs] = cq_perm
        local_node = np.zeros(N, dtype=np.int32)
        local_node[:C] = local_cq
        for s, cohs in enumerate(cohorts_of):
            for j, nd in enumerate(cohs):
                node_perm[s, Cs + j] = nd
                local_node[nd] = Cs + j
        forest_perm = np.full((S, Gs), -1, dtype=np.int32)
        for s, fl in enumerate(forests_of):
            for j, g in enumerate(fl):
                forest_perm[s, j] = g
        self.cq_perm = cq_perm
        self.cq_pos = cq_pos
        self.node_perm = node_perm
        self.forest_perm = forest_perm

        # value-remapped static tables (slot positions preserved)
        members_l = np.full((S * Gs, L), -1, dtype=np.int32)
        cand_rows_l = np.full((S * Gs, KC), -1, dtype=np.int32)
        cand_lmem_l = np.zeros((S * Gs, KC), dtype=np.int32)
        for s, fl in enumerate(forests_of):
            for j, g in enumerate(fl):
                r = s * Gs + j
                mrow = members[g]
                mv = mrow >= 0
                members_l[r][mv] = local_cq[mrow[mv]]
                crow = cand_rows[g]
                cv = crow >= 0
                crs = crow[cv]
                cand_rows_l[r][cv] = (local_cq[crs // M] * M
                                      + crs % M).astype(np.int32)
                cand_lmem_l[r] = cand_lmem[g]
        parent_l = np.full(S * Ns, -1, dtype=np.int32)
        node_level_l = np.zeros(S * Ns, dtype=np.int32)
        flat_nodes = node_perm.ravel()
        nv = flat_nodes >= 0
        pv = parent[flat_nodes[nv]]
        parent_l[nv] = np.where(pv >= 0, local_node[np.maximum(pv, 0)],
                                -1).astype(np.int32)
        node_level_l[nv] = node_level[flat_nodes[nv]]
        forest_of_cq_l = np.zeros(S * Cs, dtype=np.int32)
        fc = cq_perm.ravel()
        cvv = fc >= 0
        forest_of_cq_l[cvv] = local_forest[forest_of_cq[fc[cvv]]]
        self._static = {
            "members": members_l, "cand_rows": cand_rows_l,
            "cand_lmem": cand_lmem_l, "parent": parent_l,
            "node_level": node_level_l, "forest_of_cq": forest_of_cq_l,
        }

    # -- per-shard-timed permutation helpers ---------------------------
    def _permute(self, src, perm, fill, timers):
        import time as _time
        S, B = perm.shape
        out = np.full((S * B,) + src.shape[1:], fill, dtype=src.dtype)
        for s in range(S):
            t0 = _time.perf_counter()
            row = perm[s]
            v = row >= 0
            out[s * B:(s + 1) * B][v] = src[row[v]]
            if timers is not None and s < len(timers):
                timers[s] += _time.perf_counter() - t0
        return out

    def permute_rows(self, arr, fill=0, timers=None):
        """[C, ...] → [S*Cs, ...] (pad rows filled)."""
        return self._permute(np.asarray(arr), self.cq_perm, fill, timers)

    def permute_nodes(self, arr, fill=0, timers=None):
        """[N, ...] → [S*Ns, ...] (CQ nodes first per shard)."""
        return self._permute(np.asarray(arr), self.node_perm, fill,
                             timers)

    def permute_state(self, state, timers=None):
        """The 9-tuple of scan-state arrays, global → shard layout."""
        return tuple(
            self.permute_rows(arr, fill, timers)
            for arr, fill in zip(state, _STATE_FILLS))

    def permute_ext(self, ext_release, ext_unpark):
        """Event schedules [K, C, F] / [K, G] → shard layout on axis 1."""
        def ax1(arr, perm, fill):
            flat = perm.ravel()
            out = np.full((arr.shape[0], flat.size) + arr.shape[2:],
                          fill, dtype=arr.dtype)
            v = flat >= 0
            out[:, v] = arr[:, flat[v]]
            return out
        return (ax1(np.asarray(ext_release), self.cq_perm, 0),
                ax1(np.asarray(ext_unpark), self.forest_perm, False))

    def static_arrays(self, plan, timers=None):
        """The permuted STATIC-tier planes: the value-remapped layout
        tables plus every input that is a pure function of (structure
        generation, M, KC).  Cached on the layout — valid for its whole
        lifetime, which is exactly one (generation, C, M, G, L, KC)."""
        cached = getattr(self, "_static_host", None)
        if cached is not None:
            return cached
        a = plan.arrays
        out = dict(self._static)
        for name in _ROW_STATIC:
            out[name] = self.permute_rows(a[name], _C_FILLS[name], timers)
        for name, fill in _N_FILLS.items():
            out[name] = self.permute_nodes(a[name], fill, timers)
        self._static_host = out
        return out

    def plan_arrays(self, plan, timers=None):
        """The permuted kernel-input dict for ``plan``, cached on the
        plan object (chained windows reuse it untouched).  Scan-state
        planes flow through permute_state, not this dict."""
        cached = getattr(plan, "_shard_arrays", None)
        if cached is not None and cached[0] is self:
            return cached[1]
        a = plan.arrays
        out = dict(self.static_arrays(plan, timers))
        for name in SCATTER_PLANES + GLOBAL_PLANES:
            if name in _STATE_NAMES:
                continue   # scan state flows through permute_state
            out[name] = self.permute_rows(a[name], _C_FILLS[name], timers)
        plan._shard_arrays = (self, out)
        return out


def sharded_burst_fn(mesh: Mesh, *, K: int, depth: int, L: int, S: int,
                     KC: int, n_levels: int, G: int, runtime: int):
    """shard_map-wrapped fused burst kernel over the 1-D ``cq`` axis.

    Every input whose leading axis is CQ-, node- or forest-indexed is
    split across shards; the event schedules split on axis 1; seq_base
    is replicated.  The per-cycle decision planes come back concatenated
    on the CQ axis, the dirty flags replicated (the kernel psums them),
    and the final carry stays sharded on device for window chaining."""
    from functools import partial as _partial
    from ..ops.burst import _burst_cycles

    row = P("cq")
    rep = P()
    kc = P(None, "cq")
    in_specs = (row,) * 15 + (rep,) + (row,) * 26 + (kc, kc)
    out_specs = (kc, kc, kc, kc, kc, kc, rep, rep, (row,) * 9)
    body = _partial(_burst_cycles, K=K, depth=depth, L=L, S=S, KC=KC,
                    n_levels=n_levels, G=G, runtime=runtime,
                    axis_name="cq")
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

def make_hybrid_mesh(n_hosts: int | None = None, devices=None) -> Mesh:
    """A two-tier (wl, cq) mesh laid out so collective traffic matches
    the interconnect hierarchy (the DCN story for SURVEY §5.8; reference
    analog: MultiKueue spreading managers across clusters).

    The admit scan's carried usage tensor triggers per-step collectives
    on the ``cq`` axis, so that axis is pinned WITHIN a host — its
    reduce/gather traffic rides ICI.  The ``wl`` axis needs one
    all-gather per cycle (head slices back to the scan), so it is the
    axis that spans hosts over DCN: slow-link traffic is paid once per
    cycle, not once per scan step.  This mirrors the scaling-book recipe
    of mapping the highest-frequency collective to the fastest axis.

    On a real multi-host platform hosts are discovered from
    ``device.process_index``; ``n_hosts`` partitions a single-process
    (or virtual CPU) device list into equal groups for testing the
    layout without multi-host hardware.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n_hosts is None:
        by_host: dict[int, list] = {}
        for d in devices:
            by_host.setdefault(getattr(d, "process_index", 0), []).append(d)
        groups = [by_host[k] for k in sorted(by_host)]
    else:
        if n % n_hosts:
            raise ValueError(f"{n} devices do not split into {n_hosts} hosts")
        per = n // n_hosts
        groups = [list(devices[i * per:(i + 1) * per])
                  for i in range(n_hosts)]
    local = len(groups[0])
    if any(len(g) != local for g in groups):
        raise ValueError("hosts expose unequal device counts")
    # cq axis = one whole host (the quota plane and its per-step
    # collectives live entirely on that host's ICI); wl axis = hosts
    dev_array = np.asarray(
        [np.asarray(g) for g in groups])          # [hosts, local]
    return Mesh(dev_array, axis_names=("wl", "cq"))
