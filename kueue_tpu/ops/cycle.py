"""The batched admission cycle: vectorized nominate + sequential admit scan.

Phase 1 (vectorized nominate): classify every head against every flavor
slot at once — Fit / Preempt-capable / NoFit — mirroring
findFlavorForPodSetResource (flavorassigner.go:499) under the default
FlavorFungibility policy.  Production runs this phase in numpy on the host
(``classify_np``): it is O(W·S·R) array math, and keeping it host-side
avoids a device round-trip before the admit scan is dispatched.

Phase 2 (``admit_scan``): the sequential admit loop as one jitted
``lax.scan`` over the cycle order.  Assignments are FIXED at nominate time
(phase 1) — each step only re-checks that the chosen slot still fits under
the usage mutated by earlier steps, exactly like the reference admit loop
(scheduler.go:245 fits re-check; it never re-runs flavor assignment).
Preempt-classified entries with no preemption candidates reserve capacity
(resourcesToReserve, scheduler.go:383-408) so later entries can't jump
ahead.

``solve_cycle`` / ``solve_cycle_forests`` keep the one-call probe/test
surface (phase 1 + scan in a single jitted program).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import span as _span
from .quota_kernel import available_all, available_at, add_usage_chain


# ----------------------------------------------------------------------
# Host-side (numpy) phase 1
# ----------------------------------------------------------------------

def available_all_np(usage, subtree, guaranteed, borrow_cap, has_blim,
                     parent, depth: int) -> np.ndarray:
    """numpy twin of quota_kernel.available_all (resource_node.go:89)."""
    is_root = (parent < 0)[:, None]
    parent_safe = np.maximum(parent, 0)
    root_avail = subtree.astype(np.int64) - usage
    local = np.maximum(0, guaranteed.astype(np.int64) - usage)
    used_in_parent = np.maximum(0, usage.astype(np.int64) - guaranteed)
    blim_cap = borrow_cap.astype(np.int64) - used_in_parent
    avail = root_avail.copy()
    for _ in range(depth):
        parent_avail = avail[parent_safe]
        parent_avail = np.where(has_blim, np.minimum(blim_cap, parent_avail),
                                parent_avail)
        avail = np.where(is_root, root_avail, local + parent_avail)
    return avail


def walk_groups(xp, *, req, frs, grp, slot_ok, eligible, slot_count, start,
                av, pot, nom, use, sq, can_preempt_borrow, has_parent,
                wcb, wcp, valid):
    """The flavor walk of B heads, one pass a PodSet in the Workload's
    order, in each pass one walk a resource group, and the join of a
    head's PodSets and groups: the one statement of assignFlavors and
    findFlavorForPodSetResource (flavorassigner.go:499) for the device
    path, in numpy (``classify_np``) or ``xp=jax.numpy`` (the jitted
    ``solve_cycle`` and the fused window, ops/burst.py).

    A resource belongs to one group of its queue (``grp`` [B, R], -1:
    none covers it), and ``frs`` [B, S, R] names flavor s *of that
    group* for resource r, so every plane a (slot, resource) is a
    group's already; a pass reduces it over each group's own
    resources to planes [B, G, S] and runs G walks side by side: from
    the group's own start slot (``start`` [B, P, G]), over the group's
    own flavors (``slot_ok``, ``slot_count``), passing over what the
    PodSet may not take in that group (``eligible`` [B, P, G, S]),
    under the queue's stop rules.  A group none of whose resources the
    PodSet requests is not walked.

    PodSet p (``req`` [B, P, R]: its total request; all 0 where the
    head has fewer) is tested at ``val = req[p] + acc``, where ``acc``
    [B, S, R] is what the head's earlier PodSets chose on that (slot,
    resource): the usage the host's assignment carries from PodSet to
    PodSet.  A PodSet is as good as its worst walked group (a requested
    resource no group covers is NoFit) and a NoFit PodSet ends the
    head's walk; the head is as good as its worst PodSet, borrows if
    any walk does, and takes one slot a (PodSet, group).  P = 1 is one
    pass at ``val = req``.

    ``av`` / ``pot`` / ``nom`` / ``use`` / ``sq`` [B, S, R] are the
    head's queue's available, potential, nominal, usage and subtree
    quota at ``frs``.  Returns a dict; see ``classify_np``."""
    B, S, R = frs.shape
    G = slot_ok.shape[1]
    P = req.shape[1]
    covered = frs >= 0
    in_g = grp[:, None, :] == xp.arange(G, dtype=grp.dtype)[None, :, None]
    sel = in_g[:, :, None, :]                               # [B,G,1,R]
    grp_safe = xp.maximum(grp, 0)
    in_any = (grp >= 0)[:, None, :]
    sidx = xp.arange(S, dtype=xp.int32)[None, None, :]
    slot_r = xp.arange(S, dtype=xp.int32)[None, :, None]    # [1,S,1]
    own_g = grp_safe[:, None, :] + xp.zeros((1, S, 1), dtype=grp_safe.dtype)

    def any_g(x):           # [B,S,R] -> [B,G,S] over the group's own
        return xp.any(x[:, None] & sel, axis=3)

    def of_res(x):          # [B,G,S] -> [B,S,R]: r reads its own group's
        return xp.take_along_axis(xp.swapaxes(x, 1, 2), own_g, axis=2)

    acc = None              # [B,S,R] the earlier PodSets' choices
    alive = valid           # no earlier PodSet was NoFit
    passes = []
    for p in range(P):
        own = req[:, p][:, None, :]                         # [B,1,R]
        val = own if acc is None else own + acc
        needed = own > 0
        uncovered = xp.any(needed[:, 0, :] & (grp < 0), axis=1)
        walked = xp.any(needed & in_g, axis=2)              # [B,G]

        relevant = covered & needed
        fit_r = val <= av
        nofit_r = val > pot
        preempt_capable_r = (val <= nom) | can_preempt_borrow[:, None, None]
        res_nofit = relevant & (nofit_r | (~fit_r & ~preempt_capable_r))
        borrow_r = relevant & (use + val > sq)

        missing = any_g(needed & ~covered)
        ok_s = slot_ok & eligible[:, p]
        barred = slot_ok & ~eligible[:, p]
        fit_s = ~any_g(relevant & ~fit_r) & ~missing & ok_s  # [B,G,S]
        nofit_s = any_g(res_nofit) | missing | ~ok_s
        preempt_s = ~fit_s & ~nofit_s
        borrows_s = any_g(borrow_r) & has_parent[:, None, None]

        # the fungibility walk: a slot STOPS it when it fits without
        # borrowing, fits borrowing under whenCanBorrow=Borrow, or is
        # preempt-capable under whenCanPreempt=Preempt
        # (shouldTryNextFlavor, :620); else it keeps the best-mode slot
        # seen (Fit > Preempt > NoFit, first occurrence), a stop
        # overriding any earlier best
        active_s = sidx >= start[:, p][:, :, None]
        stop_s = (active_s & (fit_s | (preempt_s & wcp[:, None, None]))
                  & (~borrows_s | wcb[:, None, None]))
        has_stop = xp.any(stop_s, axis=2)                   # [B,G]
        stop_idx = xp.argmax(stop_s, axis=2).astype(xp.int32)
        act_mode = xp.where(
            active_s, xp.where(fit_s, 2, xp.where(preempt_s, 1, 0)), 0)
        best_mode = act_mode.max(axis=2)
        best_idx = xp.argmax((act_mode == best_mode[:, :, None]) & active_s,
                             axis=2).astype(xp.int32)
        chosen = xp.where(has_stop, stop_idx, best_idx)     # [B,G]
        at = chosen[:, :, None]
        chosen_mode = xp.take_along_axis(act_mode, at, axis=2)[:, :, 0]
        chosen_borrows = (xp.take_along_axis(borrows_s, at, axis=2)[:, :, 0]
                          & walked)
        # the resume state the host records for a walk: the stop slot
        # when it stopped mid-list, else -1 (whole list attempted)
        tried = xp.where(walked & has_stop & (chosen < slot_count - 1),
                         chosen, -1)
        preempt_slots = preempt_s & active_s                # [B,G,S]

        # each resource reads its own group's slot
        res_slot = xp.take_along_axis(chosen, grp_safe, axis=1)  # [B,R]
        rs = res_slot[:, None, :]
        res_fr = xp.where(grp >= 0,
                          xp.take_along_axis(frs, rs, axis=1)[:, 0, :], -1)
        slot_res_fit = fit_r | ~relevant                    # [B,S,R]
        res_fit = xp.take_along_axis(slot_res_fit, rs, axis=1)[:, 0, :]
        oracle_ask = (of_res(preempt_slots) & in_any & relevant & ~fit_r
                      & (val <= nom) & (use + val <= sq))

        last = xp.where(has_stop, stop_idx + 1, slot_count)
        counted = walked & alive[:, None]
        visited = active_s & (sidx < last[:, :, None])
        mode_g = xp.where(walked, chosen_mode, 2)
        mode = xp.where(uncovered, 0, mode_g.min(axis=1))   # [B]
        entered = alive & xp.any(needed[:, 0, :], axis=1)
        passes.append({
            "chosen": chosen, "walked": walked, "tried": tried,
            "has_stop": has_stop, "chosen_mode": chosen_mode,
            "preempt_slots": preempt_slots, "res_fr": res_fr,
            "res_fit": res_fit, "slot_res_fit": slot_res_fit,
            "slot_borrows": borrows_s, "oracle_ask": oracle_ask,
            "mode": mode, "borrows": xp.any(chosen_borrows, axis=1),
            # the host's assignment holds a record of this PodSet: it
            # was reached, and it got its flavors
            "recorded": alive & (mode > 0),
            "preempt_count": preempt_slots.sum(axis=2),
            "walk_slots": xp.where(
                counted, xp.maximum(last - start[:, p], 0), 0).sum(axis=1),
            "walk_ineligible": xp.where(
                counted, (barred & visited).sum(axis=2), 0).sum(axis=1),
            "group_walks": counted.sum(axis=1),
            "entered": entered,
            # the pass met, on a slot it visited, usage an earlier
            # PodSet of the same head had put there
            "charged": (entered & xp.any(
                of_res(visited) & relevant & (acc > 0), axis=(1, 2))
                if acc is not None else xp.zeros_like(entered)),
            "lo": mode_g.min(axis=1),
            "hi": xp.where(walked, chosen_mode, 0).max(axis=1),
        })
        put = xp.where((slot_r == rs) & in_any, own, 0)     # [B,S,R]
        acc = put if acc is None else acc + put
        alive = alive & (mode > 0)

    def stack(name):
        return xp.stack([d[name] for d in passes], axis=1)

    def total(name):
        return sum((d[name] for d in passes[1:]), passes[0][name])

    # -- the join ------------------------------------------------------
    head_mode = xp.where(valid, stack("mode").min(axis=1), 0)
    has_fit = head_mode == 2
    has_preempt = head_mode == 1
    chosen, walked = stack("chosen"), stack("walked")       # [B,P,G]
    has_stop = stack("has_stop")
    pre_g = (walked & (stack("chosen_mode") == 1)
             & has_preempt[:, None, None])
    # a policy-stopped preempt choice is final, and so is the only
    # preempt-capable slot; with several, the group's pick is the
    # reclaim oracle's (flavorassigner.go:692 RECLAIM beats PREEMPT)
    oracle_groups = pre_g & ~has_stop & (stack("preempt_count") > 1)
    lo = stack("lo").min(axis=1)
    hi = stack("hi").max(axis=1)
    # PodSets of one head on different flavors of one group
    first = xp.where(walked, chosen, S).min(axis=1)         # [B,G]
    final = xp.where(walked, chosen, -1).max(axis=1)
    return {
        "has_fit": has_fit, "has_preempt": has_preempt,
        "borrows": xp.any(stack("borrows"), axis=1), "chosen": chosen,
        "walked": walked, "tried": stack("tried"), "has_stop": has_stop,
        "pre_g": pre_g, "oracle_groups": oracle_groups,
        "preempt_slots": stack("preempt_slots"),
        "res_fr": stack("res_fr"), "res_fit": stack("res_fit"),
        "slot_res_fit": stack("slot_res_fit"),
        "slot_borrows": stack("slot_borrows"),
        "oracle_ask": stack("oracle_ask"),
        "walk_slots": total("walk_slots"),
        "walk_ineligible": total("walk_ineligible"),
        "group_walks": total("group_walks"),
        "split_mode": valid & xp.any(walked, axis=(1, 2)) & (lo != hi),
        "podset_walks": stack("entered").sum(axis=1),
        "charged_walks": stack("charged").sum(axis=1),
        "split_flavor": (has_fit | has_preempt) & xp.any(
            (final >= 0) & (first != final), axis=1),
        "recorded": stack("recorded"),
    }


def classify_np(packed, avail0=None, potential0=None, start_slot=None,
                eligible=None):
    """Vectorized nominate on the host: per-head, per-PodSet, per-group
    slot classification (``walk_groups`` over the cycle's heads).

    ``start_slot`` [W, P, G] carries the fungibility resume index a
    (PodSet, group) (last_tried_flavor_idx + 1 of the group's first
    requested resource); slots below it are never attempted.
    ``eligible`` [W, P, G, S] is False where the PodSet may not take the
    flavor for a taint or a selector (ops/eligibility.py): the walk
    visits such a slot and passes on, as over a flavor that does not
    exist; it is NoFit for that PodSet, no stop, no preempt-capable
    slot and nothing to ask the oracle about.

    Returns a dict of [W]-shaped arrays unless noted:
      fit0          the head fits: every walked group of every PodSet
                    chose a Fit slot
      slots0        [W, P, G] the slot each walk chose (-1: the head is
                    NoFit); Fit slots of a fit head, and of a preempt
                    head the Fit slots of its fitting walks beside the
                    preempt slots of the others; no resource reads the
                    slot of a group that was not walked
      fit_slot0     the one-PodSet, one-group reading: the first
                    PodSet's group 0's slot of a fit head, else -1
      borrows0      the fit assignment borrows (in any walk)
      preempt0      no NoFit walk, and some walk chose a
                    preempt-capable slot
      preempt_borrows0  that preempt assignment borrows (in any walk)
      preempt_res_fit   [W, P, R] per-resource Fit flag on the slot of
                    the resource's group, at the PodSet's ``val``
                    (False ⇒ the pair is one needing preemption)
      preempt_stopped0  every preempt-choosing walk STOPPED at its slot
                    (the choice is policy-forced, independent of the
                    reclaim oracle)
      oracle_groups [W, P, G] the walks that met several
                    preempt-capable slots and no stop: the pick among
                    them is the reclaim oracle's (``pick_preempt_slot_np``)
      preempt_slots [W, P, G, S] the attempted preempt-capable slots
      slot_res_fit  [W, P, S, R] per-resource Fit flag on every slot
      slot_borrows  [W, P, G, S] an assignment on the slot borrows
      oracle_ask    [W, P, S, R] the resources of ``preempt_slots`` on
                    which the host walk asks the oracle: short of quota,
                    within nominal, and not borrowing with ``val``
                    (flavorassigner.go:692, preemption_oracle.go:40)
      tried         [W, P, G] the resume state the host records a walk:
                    the stop slot when it stopped mid-list, else -1
      walk_slots    flavors the head's walks visited: in each up to its
                    stop slot, or the whole list from its start
      walk_ineligible  of them, the flavors the PodSet may not take
      group_walks   the (PodSet, group) walks
      split_mode    the head's walks ended in different modes
      podset_walks  the head's PodSet passes (up to its first NoFit one)
      charged_walks of them, those that met an earlier PodSet's usage
                    on a slot they visited
      split_flavor  a fit or preempt head whose PodSets chose different
                    flavors in one group
    """
    st = packed.structure
    usage0 = packed.usage0
    if avail0 is None:
        avail0 = available_all_np(
            usage0, st.subtree_quota, st.guaranteed, st.borrow_cap,
            st.has_borrow_limit, st.parent, st.depth)
    if potential0 is None:
        potential0 = available_all_np(
            np.zeros_like(usage0), st.subtree_quota, st.guaranteed,
            st.borrow_cap, st.has_borrow_limit, st.parent, st.depth)

    wl_cq = packed.wl_cq
    cqs = np.maximum(wl_cq, 0)
    frs = st.slot_fr[cqs]                                   # [W,S,R]
    at = (cqs[:, None, None], np.maximum(frs, 0))
    W, G = len(cqs), st.n_groups
    S, R = frs.shape[1:]
    req = packed.wl_requests.astype(np.int64).reshape(W, -1, R)
    P = req.shape[1]
    valid = wl_cq >= 0
    with _span("cycle.nominate.classify.podsets"):
        with _span("cycle.nominate.classify.groups"):
            out = walk_groups(
                np, req=req, frs=frs,
                grp=st.res_group[cqs], slot_ok=st.slot_valid[cqs],
                eligible=(np.ones((W, P, G, S), dtype=bool)
                          if eligible is None
                          else np.asarray(eligible).reshape(W, P, G, S)),
                slot_count=st.slot_count_cq[cqs],
                start=(np.zeros((W, P, G), dtype=np.int32)
                       if start_slot is None
                       else np.asarray(start_slot, dtype=np.int32
                                       ).reshape(W, P, G)),
                av=avail0[at], pot=potential0[at], nom=st.nominal_cq[at],
                use=usage0[at], sq=st.subtree_quota[at],
                can_preempt_borrow=st.cq_can_preempt_borrow[cqs],
                has_parent=st.parent[cqs] >= 0, wcb=st.cq_wcb_borrow[cqs],
                wcp=st.cq_wcp_preempt[cqs], valid=valid)
    has_fit, has_preempt = out["has_fit"], out["has_preempt"]
    chosen = out["chosen"]
    decided = (has_fit | has_preempt)[:, None, None]
    return {
        "fit0": has_fit,
        "slots0": np.where(decided, chosen, -1).astype(np.int32),
        "fit_slot0": np.where(has_fit, chosen[:, 0, 0], -1).astype(np.int32),
        "borrows0": out["borrows"] & has_fit,
        "preempt0": has_preempt,
        "preempt_borrows0": out["borrows"] & has_preempt,
        "preempt_res_fit": out["res_fit"],
        "preempt_stopped0": has_preempt & ~np.any(
            out["pre_g"] & ~out["has_stop"], axis=(1, 2)),
        "oracle_groups": out["oracle_groups"],
        "preempt_slots": out["preempt_slots"],
        "slot_res_fit": out["slot_res_fit"],
        "slot_borrows": out["slot_borrows"],
        "oracle_ask": out["oracle_ask"],
        "walked": out["walked"],
        "tried": out["tried"].astype(np.int32),
        "walk_slots": out["walk_slots"].astype(np.int32),
        "walk_ineligible": out["walk_ineligible"].astype(np.int32),
        "group_walks": out["group_walks"].astype(np.int32),
        "split_mode": out["split_mode"],
        "podset_walks": out["podset_walks"].astype(np.int32),
        "charged_walks": out["charged_walks"].astype(np.int32),
        "split_flavor": out["split_flavor"],
        "avail0": avail0,
        "potential0": potential0,
    }


def pick_preempt_slot_np(preempt_slots, slot_res_fit, reclaim,
                         in_group=None) -> np.ndarray:
    """One group's pick among several preempt-capable slots, once the
    reclaim oracle has answered (flavorassigner.go:308 granular modes):
    a resource of the group (``in_group`` [W, R]; omitted, every
    resource) short of quota is
    Reclaim where ``reclaim`` [W, S, R] says the quota can be had from
    other queues' borrowers alone, else Preempt; a slot is as good as
    its worst resource (Fit > Reclaim > Preempt) and the first slot of
    the best mode wins.  ``preempt_slots`` [W, S] are the group's.
    Returns [W] slots; a row without a preempt-capable slot reads 0."""
    if in_group is not None:
        slot_res_fit = slot_res_fit | ~in_group[:, None, :]
    res_mode = np.where(slot_res_fit, 3, np.where(reclaim, 2, 1))
    mode = np.where(preempt_slots, res_mode.min(axis=2), 0)
    return np.argmax(mode == mode.max(axis=1, keepdims=True),
                     axis=1).astype(np.int32)


def cycle_order_np(borrows, priority, timestamp) -> np.ndarray:
    """entryOrdering.Less (scheduler.go:567): borrows asc, priority desc,
    timestamp asc, stable."""
    W = len(priority)
    return np.lexsort((np.arange(W), timestamp, -priority,
                       borrows.astype(np.int32))).astype(np.int32)


# ----------------------------------------------------------------------
# Device admit scan (fixed assignments; the production phase 2)
# ----------------------------------------------------------------------

def _entry_decision(avail_row, usage, wi, valid, *, nominal_cq, npb_cq,
                    wl_cq, dec_fr, dec_amt, fit_mask, res_fr, res_amt,
                    res_mask, res_borrows):
    """The per-entry decision shared by admit_scan and admit_scan_forests:
    fixed-assignment fit re-check (scheduler.go:372, Fits over
    assignment.Usage) or capacity reserve (resourcesToReserve,
    scheduler.go:383-408).

    Decisions are (flavor-resource, amount) pairs [K] per head — exactly
    the assignment.Usage map the reference re-checks — so multi-resource-
    group and multi-PodSet assignments need no special casing here.  The
    packer guarantees each head's pairs have distinct flavor-resources.

    Returns (admit, node, delta_f): node is the CQ to charge (-1 = no-op)."""
    wis = jnp.maximum(wi, 0)
    cq = jnp.maximum(wl_cq[wis], 0)
    F = usage.shape[1]

    frs = dec_fr[wis]                                       # [K]
    amt = dec_amt[wis]
    frs_safe = jnp.maximum(frs, 0)
    relevant = frs >= 0
    ok = jnp.all(jnp.where(relevant, amt <= avail_row[frs_safe], True))
    admit = fit_mask[wis] & valid & ok
    delta_f = jnp.zeros(F, dtype=usage.dtype).at[frs_safe].add(
        jnp.where(relevant & admit, amt, 0))

    is_res = res_mask[wis] & valid
    rfrs = res_fr[wis]
    ramt = res_amt[wis]
    rfrs_safe = jnp.maximum(rfrs, 0)
    rrel = rfrs >= 0
    cur = usage[cq][rfrs_safe]
    res_borrow = jnp.minimum(ramt, npb_cq[cq][rfrs_safe] - cur)
    res_nob = jnp.maximum(0, jnp.minimum(ramt, nominal_cq[cq][rfrs_safe] - cur))
    rdelta = jnp.where(res_borrows[wis], res_borrow, res_nob)
    delta_f = delta_f.at[rfrs_safe].add(
        jnp.where(rrel & is_res, rdelta, 0))

    node = jnp.where(admit | is_res, wl_cq[wis], -1)
    return admit, node, delta_f


def _admit_step(usage, wi, *, subtree, guaranteed, borrow_cap, has_blim,
                parent, nominal_cq, npb_cq, wl_cq, dec_fr, dec_amt,
                fit_mask, res_fr, res_amt, res_mask, res_borrows, depth):
    """One cycle-order step: fit re-check + admit, or capacity reserve.

    Availability is computed chain-locally for the entry's CQ only
    (O(depth·F) per step, not O(N·F)) — the fits re-check never looks at
    another CQ's row."""
    cq = jnp.maximum(wl_cq[jnp.maximum(wi, 0)], 0)
    avail_row = available_at(usage, subtree, guaranteed, borrow_cap,
                             has_blim, parent, cq, depth)
    admit, node, delta_f = _entry_decision(
        avail_row, usage, wi, wl_cq[wi] >= 0,
        nominal_cq=nominal_cq, npb_cq=npb_cq, wl_cq=wl_cq,
        dec_fr=dec_fr, dec_amt=dec_amt, fit_mask=fit_mask,
        res_fr=res_fr, res_amt=res_amt, res_mask=res_mask,
        res_borrows=res_borrows)
    usage = add_usage_chain(usage, node, delta_f, guaranteed, parent, depth)
    return usage, admit


@partial(jax.jit, static_argnames=("depth",))
def admit_scan(usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
               nominal_cq, npb_cq, wl_cq, dec_fr, dec_amt, fit_mask,
               res_fr, res_amt, res_mask, res_borrows,
               order, *, depth: int):
    """The sequential admit loop over ``order`` as one lax.scan.

    Returns admitted[W] (original head order).  Decision-identical to the
    host admit loop for cycles whose preempt entries all have zero
    preemption candidates (the solver checks that before dispatching)."""
    W = wl_cq.shape[0]
    step = partial(_admit_step, subtree=subtree, guaranteed=guaranteed,
                   borrow_cap=borrow_cap, has_blim=has_blim, parent=parent,
                   nominal_cq=nominal_cq, npb_cq=npb_cq, wl_cq=wl_cq,
                   dec_fr=dec_fr, dec_amt=dec_amt, fit_mask=fit_mask,
                   res_fr=res_fr, res_amt=res_amt, res_mask=res_mask,
                   res_borrows=res_borrows, depth=depth)
    _, admit_o = jax.lax.scan(step, usage0, order)
    return jnp.zeros(W, dtype=bool).at[order].set(admit_o)


# ----------------------------------------------------------------------
# Preemption-aware admit scan (cycles whose preempt heads have targets)
# ----------------------------------------------------------------------

def _remove_usage_chain(usage, node, delta, guaranteed, parent, depth):
    """remove_usage bubbling up one ancestor chain (resource_node.go:135)."""
    def body(i, state):
        usage, cur, carry = state
        valid = cur >= 0
        cur_safe = jnp.maximum(cur, 0)
        stored_in_parent = usage[cur_safe] - guaranteed[cur_safe]
        sub = jnp.where(valid, carry, 0)
        usage = usage.at[cur_safe].add(-sub)
        next_carry = jnp.where(stored_in_parent > 0,
                               jnp.minimum(carry, stored_in_parent), 0)
        next_cur = jnp.where(valid, parent[cur_safe], -1)
        return usage, next_cur, jnp.where(valid, next_carry, carry)

    usage, _, _ = jax.lax.fori_loop(
        0, depth, body, (usage, node.astype(jnp.int32), delta))
    return usage


def _preempt_entry_decision(usage, usage_check, used, wi, valid,
                            *, nominal_cq, npb_cq, wl_cq, dec_fr, dec_amt,
                            fit_mask, res_fr, res_amt, res_mask,
                            res_borrows, preempt_mask, pre_fr, pre_amt,
                            tgt_mat, tu_cq, tu_delta, guaranteed, parent,
                            subtree, borrow_cap, has_blim, depth):
    """One entry of the preemption-aware admit loop.

    Mirrors the reference admit loop (scheduler.go:211-284) with
    preemptions: every fits check runs against usage minus the
    already-preempted targets (scheduler.go:372 fits under
    PreemptedWorkloads), preempt entries remove their own targets first
    (_fits_with_removal), overlapping targets skip the entry, and both
    admitted and preempting entries charge their usage forward.

    Returns (admit, preempting, overlap_skip, node, delta_f, u_try,
    used_next): ``node`` is the CQ to charge (-1 no-op); ``u_try`` is the
    check-usage after this entry's target removals (committed by the
    caller only when the entry preempts)."""
    wis = jnp.maximum(wi, 0)
    cq = jnp.maximum(wl_cq[wis], 0)
    F = usage.shape[1]
    MT = tgt_mat.shape[1]

    # --- fit entry: re-check the fixed pairs against the check state
    # (chain-local availability at the entry's CQ only) ---
    avail_check = available_at(usage_check, subtree, guaranteed, borrow_cap,
                               has_blim, parent, cq, depth)
    frs = dec_fr[wis]
    amt = dec_amt[wis]
    frs_safe = jnp.maximum(frs, 0)
    relevant = frs >= 0
    fit_ok = jnp.all(jnp.where(relevant, amt <= avail_check[frs_safe],
                               True))
    admit = fit_mask[wis] & valid & fit_ok
    delta_f = jnp.zeros(F, dtype=usage.dtype).at[frs_safe].add(
        jnp.where(relevant & admit, amt, 0))

    # --- preempt entry: overlap check + remove targets + fits ---
    is_pre = preempt_mask[wis] & valid
    tgts = tgt_mat[wis]                                    # [MT]
    t_valid = tgts >= 0
    t_safe = jnp.maximum(tgts, 0)
    overlap = jnp.any(used[t_safe] & t_valid)
    overlap_skip = is_pre & overlap
    act_pre = is_pre & ~overlap

    def rm(j, u):
        do = t_valid[j] & act_pre
        u2 = _remove_usage_chain(u, tu_cq[t_safe[j]], tu_delta[t_safe[j]],
                                 guaranteed, parent, depth)
        return jnp.where(do, u2, u)

    u_try = jax.lax.fori_loop(0, MT, rm, usage_check)
    avail_try = available_at(u_try, subtree, guaranteed, borrow_cap,
                             has_blim, parent, cq, depth)
    pfrs = pre_fr[wis]
    pamt = pre_amt[wis]
    pfrs_safe = jnp.maximum(pfrs, 0)
    p_rel = pfrs >= 0
    pre_ok = jnp.all(jnp.where(p_rel, pamt <= avail_try[pfrs_safe], True))
    preempting = act_pre & pre_ok
    pre_delta = jnp.zeros(F, dtype=usage.dtype).at[pfrs_safe].add(
        jnp.where(p_rel & preempting, pamt, 0))
    delta_f = delta_f + pre_delta
    # max-scatter: pads share index 0 with real targets; a duplicate
    # .set's winner is undefined, while max(used, mark) is order-free
    used_next = used.at[t_safe].max(t_valid & preempting)

    # --- reserve entry (unchanged semantics) ---
    is_res = res_mask[wis] & valid
    rfrs = res_fr[wis]
    ramt = res_amt[wis]
    rfrs_safe = jnp.maximum(rfrs, 0)
    rrel = rfrs >= 0
    cur = usage[cq][rfrs_safe]
    res_borrow = jnp.minimum(ramt, npb_cq[cq][rfrs_safe] - cur)
    res_nob = jnp.maximum(0, jnp.minimum(ramt, nominal_cq[cq][rfrs_safe] - cur))
    rdelta = jnp.where(res_borrows[wis], res_borrow, res_nob)
    delta_f = delta_f.at[rfrs_safe].add(jnp.where(rrel & is_res, rdelta, 0))

    node = jnp.where(admit | preempting | is_res, wl_cq[wis], -1)
    return admit, preempting, overlap_skip, node, delta_f, u_try, used_next


@partial(jax.jit, static_argnames=("depth",))
def admit_scan_preempt(usage0, subtree, guaranteed, borrow_cap, has_blim,
                       parent, nominal_cq, npb_cq, wl_cq, dec_fr, dec_amt,
                       fit_mask, res_fr, res_amt, res_mask, res_borrows,
                       preempt_mask, pre_fr, pre_amt, tgt_mat, tu_cq,
                       tu_delta, order, *, depth: int):
    """``admit_scan`` extended with preempting entries.

    Carries (usage, usage_check, used): ``usage`` follows the reference's
    live snapshot (admits + reserves + preemptor additions, targets NOT
    removed — scheduler.go:272 simulate), ``usage_check`` additionally has
    every preempted target removed (the state `fits` checks against,
    scheduler.go:372-381), ``used`` is the PreemptedWorkloads set.

    Returns (admitted[W], preempting[W], overlap_skip[W]) in head order."""
    W = wl_cq.shape[0]
    T = tu_cq.shape[0]

    def step(carry, wi):
        usage, usage_check, used = carry
        admit, preempting, overlap_skip, node, delta_f, u_try, used = (
            _preempt_entry_decision(
                usage, usage_check, used, wi, wl_cq[wi] >= 0,
                nominal_cq=nominal_cq, npb_cq=npb_cq, wl_cq=wl_cq,
                dec_fr=dec_fr, dec_amt=dec_amt, fit_mask=fit_mask,
                res_fr=res_fr, res_amt=res_amt, res_mask=res_mask,
                res_borrows=res_borrows, preempt_mask=preempt_mask,
                pre_fr=pre_fr, pre_amt=pre_amt,
                tgt_mat=tgt_mat, tu_cq=tu_cq, tu_delta=tu_delta,
                guaranteed=guaranteed, parent=parent, subtree=subtree,
                borrow_cap=borrow_cap, has_blim=has_blim, depth=depth))
        usage = add_usage_chain(usage, node, delta_f, guaranteed, parent,
                                depth)
        base_check = jnp.where(preempting, u_try, usage_check)
        usage_check = add_usage_chain(base_check, node, delta_f, guaranteed,
                                      parent, depth)
        return (usage, usage_check, used), (admit, preempting, overlap_skip)

    used0 = jnp.zeros(T, dtype=bool)
    _, (admit_o, pre_o, skip_o) = jax.lax.scan(
        step, (usage0, usage0, used0), order)
    z = jnp.zeros(W, dtype=bool)
    return (z.at[order].set(admit_o), z.at[order].set(pre_o),
            z.at[order].set(skip_o))


# ----------------------------------------------------------------------
# One-call solvers (probe / parity-test surface)
# ----------------------------------------------------------------------

def _phase1(usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
            nominal_cq, slot_fr, slot_valid, cq_can_preempt_borrow, wl_cq,
            wl_requests, cq_wcb_borrow, cq_wcp_preempt, start_slot,
            eligible, res_group, depth):
    """``solve_cycle``'s phase 1, traced into its caller's program:
    (has_fit, fit_slot0, borrows0, preempt0, res_fr [W, P, R], the
    requests as [W, P, R])."""
    C, S, R = slot_fr.shape
    W = wl_cq.shape[0]
    G = slot_valid.shape[1]
    if res_group is None:
        res_group = jnp.zeros((C, R), dtype=jnp.int32)
    if cq_wcb_borrow is None:
        cq_wcb_borrow = jnp.ones(C, dtype=bool)
    if cq_wcp_preempt is None:
        cq_wcp_preempt = jnp.zeros(C, dtype=bool)
    wl_requests = wl_requests.reshape(W, -1, R)
    P = wl_requests.shape[1]
    start_slot = (jnp.zeros((W, P, G), dtype=jnp.int32)
                  if start_slot is None else start_slot.reshape(W, P, G))
    eligible = (jnp.ones((W, P, G, S), dtype=bool) if eligible is None
                else eligible.reshape(W, P, G, S))

    avail0 = available_all(usage0, subtree, guaranteed, borrow_cap, has_blim,
                           parent, depth)
    potential0 = available_all(jnp.zeros_like(usage0), subtree, guaranteed,
                               borrow_cap, has_blim, parent, depth)
    cqs = jnp.maximum(wl_cq, 0)
    frs = slot_fr[cqs]                                      # [W,S,R]
    at = (cqs[:, None, None], jnp.maximum(frs, 0))
    out = walk_groups(
        jnp, req=wl_requests, frs=frs, grp=res_group[cqs],
        slot_ok=slot_valid[cqs], eligible=eligible,
        slot_count=jnp.sum(slot_valid, axis=2).astype(jnp.int32)[cqs],
        start=start_slot, av=avail0[at], pot=potential0[at],
        nom=nominal_cq[at], use=usage0[at], sq=subtree[at],
        can_preempt_borrow=cq_can_preempt_borrow[cqs],
        has_parent=parent[cqs] >= 0, wcb=cq_wcb_borrow[cqs],
        wcp=cq_wcp_preempt[cqs], valid=wl_cq >= 0)
    has_fit = out["has_fit"]
    fit_slot0 = jnp.where(has_fit, out["chosen"][:, 0, 0],
                          -1).astype(jnp.int32)
    borrows0 = out["borrows"] & has_fit
    preempt0 = out["has_preempt"]

    return (has_fit, fit_slot0, borrows0, preempt0, out["res_fr"],
            wl_requests)


@partial(jax.jit, static_argnames=("depth", "run_scan"))
def solve_cycle(usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
                nominal_cq, slot_fr, slot_valid, cq_can_preempt_borrow,
                wl_cq, wl_requests, wl_priority, wl_timestamp,
                cq_wcb_borrow=None, cq_wcp_preempt=None, start_slot=None,
                eligible=None, res_group=None,
                *, depth: int, run_scan: bool = True):
    """Returns (admitted[W] bool, slot[W] int32, borrows[W] bool,
    preempt_possible[W] bool, fit_slot0[W] int32, borrows0[W] bool).

    Phase 1 classifies each head once against the snapshot usage; the scan
    then admits in cycle order with a fits re-check on the FIXED slots —
    the reference admit-loop semantics (assignments are never recomputed
    within a cycle).  With ``run_scan=False`` only phase 1 runs.

    ``cq_wcb_borrow``/``cq_wcp_preempt`` [C] carry the FlavorFungibility
    policy per CQ and ``start_slot`` [W, P, G] the fungibility resume
    index a (PodSet, group); omitted, the default policy
    (whenCanBorrow=Borrow, whenCanPreempt=TryNextFlavor) walks every
    slot from 0 — the legacy classify surface.  ``wl_requests`` is
    [W, R] or a request a PodSet, [W, P, R].  ``eligible`` [W, P, G, S]
    bars a PodSet from the flavors it may not take (classify_np);
    omitted, none is barred.  ``slot_valid`` is [C, G, S] and ``res_group`` [C, R] names
    each resource's group; omitted, every resource is in group 0.  The slots returned are
    group 0's (``classify_np``'s ``fit_slot0``); the admit scan charges
    every group's."""
    W = wl_cq.shape[0]
    has_fit, fit_slot0, borrows0, preempt0, res_fr, wl_requests = _phase1(
        usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
        nominal_cq, slot_fr, slot_valid, cq_can_preempt_borrow, wl_cq,
        wl_requests, cq_wcb_borrow, cq_wcp_preempt, start_slot, eligible,
        res_group, depth)

    if not run_scan:
        zeros_b = jnp.zeros(W, dtype=bool)
        zeros_i = jnp.full(W, -1, dtype=jnp.int32)
        return zeros_b, zeros_i, zeros_b, preempt0, fit_slot0, borrows0

    order = jnp.lexsort((jnp.arange(W), wl_timestamp, -wl_priority,
                         borrows0.astype(jnp.int32)))
    no_reserve = jnp.zeros(W, dtype=bool)
    dec_fr, dec_amt = decision_pairs(res_fr, wl_requests, has_fit)
    zero_pairs = jnp.full_like(dec_fr, -1)
    admitted = admit_scan(
        usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
        nominal_cq, jnp.zeros_like(nominal_cq), wl_cq, dec_fr, dec_amt,
        has_fit, zero_pairs, jnp.zeros_like(dec_amt), no_reserve,
        no_reserve, order, depth=depth)
    slots = jnp.where(admitted, fit_slot0, -1).astype(jnp.int32)
    borrows = borrows0 & admitted
    return admitted, slots, borrows, preempt0, fit_slot0, borrows0


def decision_pairs(res_fr, wl_requests, on):
    """Per-resource flavor-resources -> decision pairs (jax or numpy).

    ``res_fr`` [W, P, R]: the flavor-resource each resource of each
    PodSet takes on the slot its own group chose.  Returns
    dec_fr/dec_amt [W, P*R]: a head's distinct flavor-resources and
    what it asks of each, for the heads ``on`` [W] (-1 / 0 elsewhere).
    Two PodSets of a head on one flavor-resource make one pair of their
    sum (the first PodSet's column), which is the assignment's usage
    map the admit scan re-checks."""
    xp = jnp if isinstance(res_fr, jnp.ndarray) else np
    W, R = res_fr.shape[0], res_fr.shape[-1]
    res_fr = res_fr.reshape(W, -1, R)
    wl_requests = wl_requests.reshape(W, -1, R)
    P = res_fr.shape[1]
    relevant = (res_fr >= 0) & (wl_requests > 0) & on[:, None, None]
    dec_fr = xp.where(relevant, res_fr, -1).astype(xp.int32)
    dec_amt = xp.where(relevant, wl_requests, 0).astype(xp.int32)
    if P > 1:
        fr = [dec_fr[:, p] for p in range(P)]               # [W,R] each
        amt = [dec_amt[:, p] for p in range(P)]
        for p in range(P):
            for q in range(p + 1, P):
                same = (fr[q] == fr[p]) & (fr[q] >= 0)
                amt[p] = amt[p] + xp.where(same, amt[q], 0)
                fr[q] = xp.where(same, -1, fr[q])
                amt[q] = xp.where(same, 0, amt[q])
        dec_fr, dec_amt = xp.stack(fr, axis=1), xp.stack(amt, axis=1)
    return dec_fr.reshape(W, P * R), dec_amt.reshape(W, P * R)


def res_slots(grp, slots):
    """[W, P, R] the slot each resource of each PodSet reads when the
    PodSet's groups take ``slots`` [W, P, G] (numpy):
    ``slots[w, p, grp[w, r]]``, of its own group (``grp`` [W, R], -1: no
    group covers the resource; reads group 0)."""
    W, P = slots.shape[:2]
    return np.take_along_axis(
        np.maximum(slots, 0),
        np.broadcast_to(np.maximum(grp, 0)[:, None, :],
                        (W, P, grp.shape[1])), axis=2)


def slot_frs(slot_fr, res_group, wl_cq, slots):
    """[W, P, R] the flavor-resource each resource of each PodSet takes
    when the PodSet's groups of the head's queue take ``slots``
    [W, P, G] (numpy)."""
    cqs = np.maximum(wl_cq, 0)
    grp = res_group[cqs]                                    # [W,R]
    frs = slot_fr[cqs[:, None, None], res_slots(grp, slots),
                  np.arange(slot_fr.shape[2])]
    return np.where((grp >= 0)[:, None, :], frs, -1)


def add_usage_chain_batched(usage, nodes, deltas, guaranteed, parent,
                            depth: int):
    """add_usage_chain for G disjoint ancestor chains at once.

    nodes: [G] int32 (-1 = no-op); deltas: [G, F] int32.  Chains in
    different cohort forests never share nodes, so the per-level
    scatter-adds commute."""
    def body(i, state):
        usage, cur, carry = state                     # [G], [G, F]
        valid = cur >= 0
        cur_safe = jnp.maximum(cur, 0)
        local_avail = jnp.maximum(0, guaranteed[cur_safe] - usage[cur_safe])
        add = jnp.where(valid[:, None], carry, 0)
        usage = usage.at[cur_safe].add(add)
        next_carry = jnp.maximum(0, carry - local_avail)
        next_cur = jnp.where(valid, parent[cur_safe], -1)
        return usage, next_cur, jnp.where(valid[:, None], next_carry, carry)

    usage, _, _ = jax.lax.fori_loop(
        0, depth, body, (usage, nodes.astype(jnp.int32), deltas))
    return usage


def _forest_schedule(order, f_w, W, G, max_forest_wl):
    """Group entries by forest, cycle order within each group → [G, L]."""
    inv_order = jnp.zeros(W, dtype=jnp.int32).at[order].set(
        jnp.arange(W, dtype=jnp.int32))
    p = jnp.lexsort((inv_order, f_w))                    # [W]
    f_sorted = f_w[p]
    pos = jnp.arange(W)
    # each segment's start = index of the first element with its forest
    # id; searchsorted on the sorted ids gives it directly.  NOT a
    # prefix max over flagged starts: lax.associative_scan miscomputes
    # under GSPMD sharding (observed on the (wl, cq) production mesh —
    # positions read partial maxima from other shards' blocks), and
    # sort-family ops gather correctly where the scan lowering does not
    seg_start = jnp.searchsorted(f_sorted, f_sorted, side="left")
    rank = (pos - seg_start).astype(jnp.int32)           # in-forest rank
    mat = jnp.full((G, max_forest_wl), -1, dtype=jnp.int32)
    # ranks beyond max_forest_wl are dropped (host sizes the bucket)
    return mat.at[f_sorted, rank].set(p.astype(jnp.int32), mode="drop")


@partial(jax.jit, static_argnames=("depth", "n_forests", "max_forest_wl"))
def admit_scan_forests(usage0, subtree, guaranteed, borrow_cap, has_blim,
                       parent, nominal_cq, npb_cq, wl_cq, dec_fr, dec_amt,
                       fit_mask, res_fr, res_amt, res_mask, res_borrows,
                       order, forest_of_node,
                       *, depth: int, n_forests: int, max_forest_wl: int):
    """``admit_scan`` parallelized over independent cohort forests.

    Quota never flows between forests, so the sequential within-cycle
    semantics only constrain workloads of the SAME forest; each scan step
    processes one workload per forest simultaneously (scatter-adds on
    disjoint chains).  Scan length drops from W to max_forest_wl — the
    lever that takes a 1k-head cycle from O(heads) to O(heads / forests).
    Decision-identical to admit_scan (tests/test_forest_scan.py)."""
    W = wl_cq.shape[0]
    G = n_forests + 1                       # + padding bucket

    f_w = jnp.where(wl_cq >= 0,
                    forest_of_node[jnp.maximum(wl_cq, 0)], n_forests)
    mat = _forest_schedule(order, f_w, W, G, max_forest_wl)

    def step(usage, col):
        wis = mat[:, col]                                # [G]

        def entry(wi):
            cq = jnp.maximum(wl_cq[jnp.maximum(wi, 0)], 0)
            avail_row = available_at(usage, subtree, guaranteed,
                                     borrow_cap, has_blim, parent, cq,
                                     depth)
            return _entry_decision(
                avail_row, usage, wi,
                (wi >= 0) & (wl_cq[jnp.maximum(wi, 0)] >= 0),
                nominal_cq=nominal_cq, npb_cq=npb_cq,
                wl_cq=wl_cq, dec_fr=dec_fr, dec_amt=dec_amt,
                fit_mask=fit_mask, res_fr=res_fr, res_amt=res_amt,
                res_mask=res_mask, res_borrows=res_borrows)

        admit, nodes, deltas = jax.vmap(entry)(wis)
        usage = add_usage_chain_batched(usage, nodes, deltas, guaranteed,
                                        parent, depth)
        return usage, (wis, admit)

    _, (wis_o, admit_o) = jax.lax.scan(step, usage0,
                                       jnp.arange(max_forest_wl))

    wis_flat = wis_o.reshape(-1)
    safe = jnp.maximum(wis_flat, 0)
    mask = wis_flat >= 0
    admitted = jnp.zeros(W, dtype=bool).at[safe].max(
        admit_o.reshape(-1) & mask)
    return admitted


@partial(jax.jit, static_argnames=("depth", "n_forests", "max_forest_wl"))
def solve_cycle_forests(usage0, subtree, guaranteed, borrow_cap, has_blim,
                        parent, nominal_cq, slot_fr, slot_valid,
                        cq_can_preempt_borrow, wl_cq, wl_requests,
                        wl_priority, wl_timestamp, forest_of_node,
                        eligible=None, res_group=None,
                        *, depth: int, n_forests: int, max_forest_wl: int):
    """One-call phase 1 + forest-parallel admit scan (probe surface)."""
    W = wl_cq.shape[0]
    has_fit, fit_slot0, borrows0, preempt0, res_fr, wl_requests = _phase1(
        usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
        nominal_cq, slot_fr, slot_valid, cq_can_preempt_borrow, wl_cq,
        wl_requests, None, None, None, eligible, res_group, depth)
    order = jnp.lexsort((jnp.arange(W), wl_timestamp, -wl_priority,
                         borrows0.astype(jnp.int32))).astype(jnp.int32)
    no_reserve = jnp.zeros(W, dtype=bool)
    dec_fr, dec_amt = decision_pairs(res_fr, wl_requests, has_fit)
    admitted = admit_scan_forests(
        usage0, subtree, guaranteed, borrow_cap, has_blim, parent,
        nominal_cq, jnp.zeros_like(nominal_cq), wl_cq, dec_fr, dec_amt,
        has_fit, jnp.full_like(dec_fr, -1), jnp.zeros_like(dec_amt),
        no_reserve, no_reserve, order, forest_of_node, depth=depth,
        n_forests=n_forests, max_forest_wl=max_forest_wl)
    slots = jnp.where(admitted, fit_slot0, -1).astype(jnp.int32)
    borrows = borrows0 & admitted
    return admitted, slots, borrows, preempt0, fit_slot0, borrows0
