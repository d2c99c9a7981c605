"""Which flavors of a resource group of its queue a PodSet may take.

The host walk skips a flavor before it looks at quota when the PodSet
does not tolerate one of the flavor's NoSchedule/NoExecute taints
(its own tolerations and the flavor's together), or when its
``node_selector`` / ``required_node_affinity`` does not match the
flavor's ``node_labels`` on the label keys that some flavor of the
group carries (flavorassigner.go:553-575, flavorSelector :640;
scheduler/flavorassigner.py ``_find_flavor_for_podset_resource``).  A
skipped flavor is visited, is no stop and no candidate.  The rule is a
group's: a selector key that only the flavors of one group carry is
matched in that group's walk and ignored in every other's.

This module states that rule once for the device path: a head's
answer is one *skip mask* a (PodSet, resource group of its queue), bit
s set when that PodSet may not take slot s of that group's flavor list:
a Workload's PodSets each carry their own selector and tolerations (a
launcher pinned to one pool, its workers to another), so each has its
own masks.  The per-cycle classify expands them into the
``[W, P, G, S]`` eligibility plane (ops/cycle.py ``classify_np``), the
fused window carries them a row (``wl_flavor_skip [C, M, P*G]``,
ops/burst.py), or one column of zeros where every flavor of the
structure is plain.  A mask is a function of the PodSet's selector,
affinity and tolerations and of the flavor list alone, so it is
evaluated once a distinct signature and flavor list, and cached on the
``Info`` under the structure generation (a flavor or queue edit bumps
it) and the planes' PodSet extent.  What stays with the host walk: a
queue with a missing flavor, a flavor that binds a topology or more
than ``MASK_BITS`` declared flavors in a group (``bind_flavor_lists``),
and a Workload with more PodSets than ``packing.MAX_POD_SETS``, a
topology request or a partial admission that does not fit whole
(``CycleSolver._scalar_mask``).
"""

from __future__ import annotations

import numpy as np

from ..api.types import taints_tolerated

# a row's mask is one byte of the fused window's grid: a queue whose
# group lists more flavors is decided on the device only while none of
# them is declared (then no head skips any)
MASK_BITS = 8


class FlavorList:
    """One distinct flavor list (a resource group's) of the structure's
    vector-decided queues: the flavors in the group's order, the label
    keys a selector is matched on in this group, and the masks evaluated
    so far, by signature."""
    __slots__ = ("flavors", "allowed_keys", "declared", "masks")

    def __init__(self, flavors: list):
        self.flavors = flavors
        self.allowed_keys = {k for f in flavors for k in f.node_labels}
        self.declared = any(f.node_labels or f.node_taints
                            for f in flavors)
        self.masks: dict[tuple, int] = {}

    def skip_mask(self, pod_set) -> int:
        mask = 0
        keys = self.allowed_keys
        for s, flavor in enumerate(self.flavors):
            ok = taints_tolerated(
                flavor.node_taints,
                list(pod_set.tolerations) + list(flavor.tolerations))
            if ok:
                labels = flavor.node_labels
                ok = (all(labels.get(k) == want
                          for k, want in pod_set.node_selector.items()
                          if k in keys)
                      and all(labels.get(k) in values for k, values in
                              pod_set.required_node_affinity.items()
                              if k in keys))
            if not ok:
                mask |= 1 << s
        return mask


def bind_flavor_lists(snapshot, st) -> None:
    """Sets on a packed structure: ``cq_vector_ok`` [C] bool, the
    distinct ``flavor_lists`` of its vector-decided queues,
    ``flavor_list_of_cq`` [C, G] (-1 where the queue has no such group
    or is not vector-decided) and ``flavors_declared`` (some list is).
    The vector classify reproduces the host flavor walk, one walk a
    resource group, for a queue whose flavors all exist and bind no
    topology; labels, taints and any FlavorFungibility policy run in
    the vector math itself.  Anything else routes the queue's heads to
    the scalar host walk."""
    C, G = len(st.cq_names), st.n_groups
    ok = np.zeros(C, dtype=bool)
    of_cq = np.full((C, G), -1, dtype=np.int32)
    lists: list[FlavorList] = []
    index: dict[tuple, int] = {}
    for ci, name in enumerate(st.cq_names):
        row = []
        for rg in snapshot.cluster_queues[name].spec.resource_groups:
            names = tuple(fq.name for fq in rg.flavors)
            li = index.get(names)
            if li is None:
                flavors = [snapshot.resource_flavors.get(n) for n in names]
                if any(f is None or f.topology_name for f in flavors):
                    li = -1
                else:
                    fl = FlavorList(flavors)
                    li = -1 if (fl.declared and len(names) > MASK_BITS) \
                        else len(lists)
                    if li >= 0:
                        lists.append(fl)
                index[names] = li
            row.append(li)
        if row and min(row) >= 0:
            ok[ci] = True
            of_cq[ci, :len(row)] = row
    st.cq_vector_ok = ok
    st.flavor_list_of_cq = of_cq
    st.flavor_lists = lists
    st.flavors_declared = any(fl.declared for fl in lists)


def mask_plane_width(st, M: int) -> int:
    """The second extent of the fused window's ``wl_flavor_skip`` plane
    ([C, width, G]): a mask a row of the [C, M] grid where some flavor
    of the structure is declared, one column of zeros (every row reads
    it) where all are plain and no row can skip any."""
    return M if st.flavors_declared else 1


def slots_of_mask(mask, S: int, xp=np):
    """Skip masks [...] -> eligibility [..., S] bool (numpy, or
    ``xp=jax.numpy`` inside a kernel).  No mask has a bit at or above
    ``MASK_BITS``, so every slot from there on reads that bit: clear."""
    bit = xp.minimum(xp.arange(S, dtype=xp.int32), MASK_BITS)
    return (mask[..., None].astype(xp.int32) >> bit) & 1 == 0


def declares(st, ci: int) -> bool:
    """Does a flavor of vector-decided queue ``ci`` carry labels or
    taints, so that its heads' masks can differ from 0?"""
    return any(li >= 0 and st.flavor_lists[li].declared
               for li in st.flavor_list_of_cq[ci].tolist())


def _signature(pod_set) -> tuple:
    return (tuple(sorted(pod_set.node_selector.items())),
            tuple(sorted((k, tuple(v)) for k, v in
                         pod_set.required_node_affinity.items())),
            tuple(pod_set.tolerations))


def skip_mask(info, st, ci: int, tally: dict | None = None) -> tuple:
    """The skip masks of ``info``'s PodSets in queue ``ci`` of
    structure ``st``, one a (PodSet, resource group), PodSet-major and
    ``st.pod_sets * st.n_groups`` long: all 0 for a queue the vector
    path does not decide or whose flavors are all plain, for the
    PodSets the head does not have, and for a head with more PodSets
    than the planes hold (the host walk's).
    ``tally["eligibility_masks_built"]`` counts the signatures
    evaluated, as against read from a cache."""
    G, P = st.n_groups, st.pod_sets
    pod_sets = info.obj.pod_sets
    if not declares(st, ci) or not pod_sets or len(pod_sets) > P:
        return (0,) * (P * G)
    gen = st.generation           # < 0: a structure nothing vouches for
    hit = getattr(info, "_flavor_skip", None)
    if hit is not None and hit[0] == gen >= 0 and hit[1] == (ci, P):
        return hit[2]
    masks = []
    for pod_set in pod_sets:
        sig = _signature(pod_set)
        for li in st.flavor_list_of_cq[ci].tolist():
            mask = 0
            if li >= 0 and st.flavor_lists[li].declared:
                fl = st.flavor_lists[li]
                mask = fl.masks.get(sig)
                if mask is None:
                    mask = fl.masks[sig] = fl.skip_mask(pod_set)
                    if tally is not None:
                        tally["eligibility_masks_built"] += 1
            masks.append(mask)
    masks = tuple(masks) + (0,) * ((P - len(pod_sets)) * G)
    info._flavor_skip = (gen, (ci, P), masks)
    return masks
