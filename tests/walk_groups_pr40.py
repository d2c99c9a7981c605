"""``ops/cycle.py`` ``walk_groups`` as it stood at PR 40, before a head's
request gained a PodSet axis: one walk a resource group and their join,
for one PodSet.  Kept as the yardstick of ``tests/test_pod_sets.py``:
the walk of PR 41 at P = 1 has to return what this returns, field by
field.  Nothing else imports it."""

def walk_groups(xp, *, req, frs, grp, slot_ok, eligible, slot_count, start,
                av, pot, nom, use, sq, can_preempt_borrow, has_parent,
                wcb, wcp, valid):
    """The flavor walk of B heads, one walk a resource group, and the
    join of a head's groups: the one statement of
    findFlavorForPodSetResource (flavorassigner.go:499) for the device
    path, in numpy (``classify_np``) or ``xp=jax.numpy`` (the jitted
    ``solve_cycle`` and the fused window, ops/burst.py).

    A resource belongs to one group of its queue (``grp`` [B, R], -1:
    none covers it), and ``frs`` [B, S, R] names flavor s *of that
    group* for resource r, so every plane a (slot, resource) is a
    group's already; the walk reduces it over each group's own
    resources to planes [B, G, S] and runs G walks side by side: from
    the group's own start slot (``start`` [B, G]), over the group's own
    flavors (``slot_ok``, ``slot_count``), passing over what the head
    may not take in that group (``eligible`` [B, G, S]), under the
    queue's stop rules.  A group none of whose resources the head
    requests is not walked.  The head is as good as its worst walked
    group (NoFit in one is NoFit; a requested resource no group covers
    is NoFit), borrows if any does, and takes one slot a group.

    ``av`` / ``pot`` / ``nom`` / ``use`` / ``sq`` [B, S, R] are the
    head's queue's available, potential, nominal, usage and subtree
    quota at ``frs``.  Returns a dict; see ``classify_np``."""
    B, S, R = frs.shape
    G = slot_ok.shape[1]
    req = req[:, None, :]                                   # [B,1,R]
    covered = frs >= 0
    needed = req > 0
    in_g = grp[:, None, :] == xp.arange(G, dtype=grp.dtype)[None, :, None]
    sel = in_g[:, :, None, :]                               # [B,G,1,R]
    uncovered = xp.any(needed[:, 0, :] & (grp < 0), axis=1)
    walked = xp.any(needed & in_g, axis=2)                  # [B,G]

    relevant = covered & needed
    fit_r = req <= av
    nofit_r = req > pot
    preempt_capable_r = (req <= nom) | can_preempt_borrow[:, None, None]
    res_nofit = relevant & (nofit_r | (~fit_r & ~preempt_capable_r))
    borrow_r = relevant & (use + req > sq)

    def any_g(x):           # [B,S,R] -> [B,G,S] over the group's own
        return xp.any(x[:, None] & sel, axis=3)

    missing = any_g(needed & ~covered)
    barred = slot_ok & ~eligible
    slot_ok = slot_ok & eligible
    fit_s = ~any_g(relevant & ~fit_r) & ~missing & slot_ok   # [B,G,S]
    nofit_s = any_g(res_nofit) | missing | ~slot_ok
    preempt_s = ~fit_s & ~nofit_s
    borrows_s = any_g(borrow_r) & has_parent[:, None, None]

    # the fungibility walk: a slot STOPS it when it fits without
    # borrowing, fits borrowing under whenCanBorrow=Borrow, or is
    # preempt-capable under whenCanPreempt=Preempt (shouldTryNextFlavor,
    # :620); else it keeps the best-mode slot seen (Fit > Preempt >
    # NoFit, first occurrence), a stop overriding any earlier best
    sidx = xp.arange(S, dtype=xp.int32)[None, None, :]
    active_s = sidx >= start[:, :, None]
    stop_s = (active_s & (fit_s | (preempt_s & wcp[:, None, None]))
              & (~borrows_s | wcb[:, None, None]))
    has_stop = xp.any(stop_s, axis=2)                       # [B,G]
    stop_idx = xp.argmax(stop_s, axis=2).astype(xp.int32)
    act_mode = xp.where(active_s,
                        xp.where(fit_s, 2, xp.where(preempt_s, 1, 0)), 0)
    best_mode = act_mode.max(axis=2)
    best_idx = xp.argmax((act_mode == best_mode[:, :, None]) & active_s,
                         axis=2).astype(xp.int32)
    chosen = xp.where(has_stop, stop_idx, best_idx)         # [B,G]
    at = chosen[:, :, None]
    chosen_mode = xp.take_along_axis(act_mode, at, axis=2)[:, :, 0]
    chosen_borrows = (xp.take_along_axis(borrows_s, at, axis=2)[:, :, 0]
                      & walked)
    # the resume state the host records for a walk: the stop slot when
    # it stopped mid-list, else -1 (whole list attempted)
    tried = xp.where(walked & has_stop & (chosen < slot_count - 1),
                     chosen, -1)

    # -- the join ------------------------------------------------------
    mode_g = xp.where(walked, chosen_mode, 2)
    head_mode = xp.where(valid & ~uncovered, mode_g.min(axis=1), 0)
    has_fit = head_mode == 2
    has_preempt = head_mode == 1
    borrows = xp.any(chosen_borrows, axis=1)
    pre_g = walked & (chosen_mode == 1) & has_preempt[:, None]
    preempt_slots = preempt_s & active_s                    # [B,G,S]
    preempt_count = preempt_slots.sum(axis=2)
    # a policy-stopped preempt choice is final, and so is the only
    # preempt-capable slot; with several, the group's pick is the
    # reclaim oracle's (flavorassigner.go:692 RECLAIM beats PREEMPT)
    oracle_groups = pre_g & ~has_stop & (preempt_count > 1)

    # each resource reads its own group's slot
    grp_safe = xp.maximum(grp, 0)
    res_slot = xp.take_along_axis(chosen, grp_safe, axis=1)  # [B,R]
    rs = res_slot[:, None, :]
    res_fr = xp.where(grp >= 0,
                      xp.take_along_axis(frs, rs, axis=1)[:, 0, :], -1)
    slot_res_fit = fit_r | ~relevant                        # [B,S,R]
    res_fit = xp.take_along_axis(slot_res_fit, rs, axis=1)[:, 0, :]
    ps_r = xp.take_along_axis(                              # [B,S,R]
        xp.swapaxes(preempt_slots, 1, 2), grp_safe[:, None, :]
        + xp.zeros((1, S, 1), dtype=grp_safe.dtype), axis=2)
    oracle_ask = (ps_r & (grp >= 0)[:, None, :] & relevant & ~fit_r
                  & (req <= nom) & (use + req <= sq))

    last = xp.where(has_stop, stop_idx + 1, slot_count)
    counted = walked & valid[:, None]
    walk_slots = xp.where(counted, xp.maximum(last - start, 0), 0)
    visited = active_s & (sidx < last[:, :, None])
    walk_ineligible = xp.where(
        counted, (barred & visited).sum(axis=2), 0)
    lo = xp.where(walked, chosen_mode, 2).min(axis=1)
    hi = xp.where(walked, chosen_mode, 0).max(axis=1)
    return {
        "has_fit": has_fit, "has_preempt": has_preempt,
        "borrows": borrows, "chosen": chosen, "walked": walked,
        "tried": tried, "has_stop": has_stop, "pre_g": pre_g,
        "oracle_groups": oracle_groups, "preempt_slots": preempt_slots,
        "res_fr": res_fr, "res_fit": res_fit,
        "slot_res_fit": slot_res_fit, "slot_borrows": borrows_s,
        "oracle_ask": oracle_ask,
        "walk_slots": walk_slots.sum(axis=1),
        "walk_ineligible": walk_ineligible.sum(axis=1),
        "group_walks": counted.sum(axis=1),
        "split_mode": valid & xp.any(walked, axis=1) & (lo != hi),
    }
