"""Topology-aware-scheduling cache: flavor → topology tree state.

Capability parity with reference pkg/cache/tas_cache.go + tas_flavor.go.
The full assignment algorithm lives in kueue_tpu.cache.tas_snapshot
(reference tas_flavor_snapshot.go).

A flavor's nodes are laid out once (``TASLayout``: the domains of every
level sorted by id, so a domain's children are contiguous and index
order is id order; the leaves' allocatable as a ``[N_leaf, R]`` plane)
and rebuilt only when a node, the topology or the flavor changes.  The
leaves' usage is a plane beside it, kept current by the same deltas
``add_usage`` receives, so a cycle's snapshot is one subtraction and no
tree is built for it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..api.types import ResourceFlavor, Topology


@dataclass
class NodeInfo:
    """A schedulable node feeding the topology tree."""
    name: str
    labels: dict[str, str] = field(default_factory=dict)
    capacity: dict[str, int] = field(default_factory=dict)  # canonical ints
    ready: bool = True


@dataclass
class FlavorTASInfo:
    flavor_name: str
    topology_name: str
    levels: list[str] = field(default_factory=list)
    node_labels: dict[str, str] = field(default_factory=dict)


@dataclass
class TASLayout:
    """One flavor's domains, level-sorted, and its leaves' allocatable."""
    levels: list[str]
    domain_ids: list[list[tuple]]     # per level, id tuples in id order
    parents: list[np.ndarray]         # parents[l]: [N_{l+1}] -> level-l index
    child_start: list[np.ndarray]     # child_start[l]: [N_l + 1] offsets
                                      # of each domain's children at l + 1
    resource_names: list[str]
    capacity: np.ndarray              # [N_leaf, R] int64
    leaf_index: dict[tuple, int] = field(default_factory=dict)

    @property
    def level_sizes(self) -> list[int]:
        return [len(d) for d in self.domain_ids]

    @property
    def leaf_ids(self) -> list[tuple]:
        return self.domain_ids[-1]

    @property
    def r_index(self) -> dict[str, int]:
        return {r: i for i, r in enumerate(self.resource_names)}

    @staticmethod
    def build(levels: list[str], nodes: list[NodeInfo],
              extra_resources=()) -> "TASLayout":
        """Nodes not labelled at every level are left out; nodes with
        one leaf id add up."""
        L = len(levels)
        by_leaf: dict[tuple, dict[str, int]] = {}
        names = set(extra_resources)
        for node in nodes:
            values = tuple(node.labels.get(lvl, "") for lvl in levels)
            if any(v == "" for v in values):
                continue
            free = by_leaf.setdefault(values, {})
            for r, cap in node.capacity.items():
                free[r] = free.get(r, 0) + cap
                names.add(r)
        leaf_ids = sorted(by_leaf)
        domain_ids = [sorted({lid[:lvl + 1] for lid in leaf_ids})
                      for lvl in range(L - 1)] + [leaf_ids]
        parents, child_start = [], []
        for lvl in range(1, L):
            up = {did: i for i, did in enumerate(domain_ids[lvl - 1])}
            par = np.array([up[did[:lvl]] for did in domain_ids[lvl]],
                           dtype=np.int32)
            parents.append(par)
            child_start.append(np.searchsorted(
                par, np.arange(len(domain_ids[lvl - 1]) + 1)).astype(
                    np.int32))
        resources = sorted(names)
        r_index = {r: i for i, r in enumerate(resources)}
        cap = np.zeros((len(leaf_ids), len(resources)), dtype=np.int64)
        for i, lid in enumerate(leaf_ids):
            for r, v in by_leaf[lid].items():
                cap[i, r_index[r]] = v
        return TASLayout(levels=list(levels), domain_ids=domain_ids,
                         parents=parents, child_start=child_start,
                         resource_names=resources, capacity=cap,
                         leaf_index={lid: i for i, lid in enumerate(leaf_ids)})

    def usage_plane(self, usage: dict[tuple, dict[str, int]]) -> np.ndarray:
        """``usage`` (leaf id -> {resource: quantity}) as a plane; leaves
        and resources the layout does not know are left out."""
        used = np.zeros_like(self.capacity)
        r_index = self.r_index
        for did, per in usage.items():
            i = self.leaf_index.get(tuple(did))
            if i is None:
                continue
            for r, v in per.items():
                ri = r_index.get(r)
                if ri is not None:
                    used[i, ri] += v
        return used


class TASCache:
    """reference pkg/cache/tas_cache.go."""

    def __init__(self):
        self.topologies: dict[str, Topology] = {}
        self.flavors: dict[str, FlavorTASInfo] = {}
        self.nodes: dict[str, NodeInfo] = {}
        # usage per flavor per leaf-domain id, canonical ints
        self.usage: dict[str, dict[tuple, dict[str, int]]] = {}
        # per flavor: its layout and the usage plane beside it, built on
        # first use after a node, topology or flavor change
        self._planes: dict[str, tuple[TASLayout, np.ndarray]] = {}

    def _invalidate(self, flavor: Optional[str] = None) -> None:
        if flavor is None:
            self._planes.clear()
        else:
            self._planes.pop(flavor, None)

    def add_topology(self, topology: Topology) -> None:
        self.topologies[topology.name] = topology
        for fi in self.flavors.values():
            if fi.topology_name == topology.name:
                fi.levels = list(topology.levels)
        self._invalidate()

    def delete_topology(self, name: str) -> None:
        self.topologies.pop(name, None)
        for fi in self.flavors.values():
            if fi.topology_name == name:
                fi.levels = []
        self._invalidate()

    def bind_flavor(self, flavor: ResourceFlavor) -> None:
        topo = self.topologies.get(flavor.topology_name or "")
        self.flavors[flavor.name] = FlavorTASInfo(
            flavor_name=flavor.name,
            topology_name=flavor.topology_name or "",
            levels=list(topo.levels) if topo else [],
            node_labels=dict(flavor.node_labels),
        )
        self.usage.setdefault(flavor.name, {})
        self._invalidate(flavor.name)

    def unbind_flavor(self, name: str) -> None:
        self.flavors.pop(name, None)
        self.usage.pop(name, None)
        self._invalidate(name)

    def add_or_update_node(self, node: NodeInfo) -> None:
        self.nodes[node.name] = node
        self._invalidate()

    def delete_node(self, name: str) -> None:
        self.nodes.pop(name, None)
        self._invalidate()

    def add_usage(self, flavor: str, domain: tuple, requests: dict[str, int],
                  sign: int = +1) -> None:
        per_flavor = self.usage.setdefault(flavor, {})
        dom = per_flavor.setdefault(domain, {})
        for rname, qty in requests.items():
            dom[rname] = dom.get(rname, 0) + sign * qty
        planes = self._planes.get(flavor)
        if planes is None:
            return
        layout, used = planes
        i = layout.leaf_index.get(tuple(domain))
        if i is None:
            return
        r_index = layout.r_index
        if any(r not in r_index for r in requests):
            self._invalidate(flavor)        # a resource the layout lacks
            return
        for rname, qty in requests.items():
            used[i, r_index[rname]] += sign * qty

    def planes(self, flavor: str) -> Optional[tuple[TASLayout, np.ndarray]]:
        """The flavor's (layout, usage plane), or None where it binds no
        topology with levels."""
        info = self.flavors.get(flavor)
        if info is None or not info.levels:
            return None
        planes = self._planes.get(flavor)
        if planes is None:
            nodes = [n for n in self.nodes.values()
                     if n.ready and all(n.labels.get(k) == v
                                        for k, v in info.node_labels.items())]
            usage = self.usage.get(flavor, {})
            layout = TASLayout.build(
                info.levels, nodes,
                extra_resources={r for per in usage.values() for r in per})
            planes = self._planes[flavor] = (layout,
                                             layout.usage_plane(usage))
        return planes

    def snapshot(self) -> dict:
        """Per-flavor topology snapshots for a scheduling cycle: each
        holds the flavor's layout and its leaves' free capacity."""
        from .tas_snapshot import TASFlavorSnapshot
        out = {}
        for fname, info in self.flavors.items():
            planes = self.planes(fname)
            if planes is None:
                continue
            layout, used = planes
            out[fname] = TASFlavorSnapshot(
                fname, info.levels, layout=layout,
                free=layout.capacity - used)
        return out
