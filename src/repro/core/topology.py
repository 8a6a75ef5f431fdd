"""Two-level topology descriptor for multi-object collectives.

The paper's world is (nodes × processes-per-node). On TPU the same structure
is (inter-group axis × intra-group axis): e.g. ("pod", chips-per-pod) across
DCN, or ("node-group", chips) across a long ICI axis. `Topology` names the
two mesh axes the collective algorithms operate over; sizes are taken from
the enclosing `shard_map` mesh at trace time.

A topology additionally carries *link metadata* per level: ``node_link``
describes the inter-group fabric and ``local_link`` the intra-group one.
Each is either a :class:`repro.core.costmodel.NetParams` preset name (e.g.
``"tpu_v5e_dcn"``) or a ``NetParams`` instance override. The algorithm
selector (``repro.core.autotune``) composes the two into one cost-model
parameterisation via ``costmodel.net_for(topo)``, so selection no longer
assumes one hardcoded network. ``from_mesh`` auto-derives the links from
the mesh's devices when not given explicitly.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Set, Tuple

#: platforms already warned about in :func:`derive_link` fallback (warn once
#: per platform per process, so calibration logs record which link rows are
#: folklore without drowning in repeats)
_FALLBACK_WARNED: Set[str] = set()

#: TPU ``device_kind`` -> (link preset within a slice, across processes or
#: slices). A TPU kind not listed here has no cost-model preset and raises.
TPU_LINKS = {
    "TPU v5 lite": ("tpu_v5e_ici", "tpu_v5e_dcn"),
}


def _axis_crossings(mesh, axis: str) -> Set[str]:
    """Boundary fields (``process_index`` / ``slice_index``) that vary along
    ``axis``, walked at the origin of all other mesh axes. Empty for
    degenerate size-1 axes (no traffic) and on any introspection failure."""
    crossed: Set[str] = set()
    try:
        idx = list(mesh.axis_names).index(axis)
        if mesh.devices.shape[idx] == 1:
            return crossed
        sel: list = [0] * mesh.devices.ndim
        sel[idx] = slice(None)
        lane = mesh.devices[tuple(sel)]
        for field in ("process_index", "slice_index"):
            vals = {getattr(d, field, None) for d in lane.flat}
            vals.discard(None)
            if len(vals) > 1:
                crossed.add(field)
    except (KeyError, ValueError, TypeError):
        pass
    return crossed


def _warn_fallback(platform: str, link: str) -> None:
    if platform in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(platform)
    warnings.warn(
        f"derive_link: no measured NetParams preset for platform "
        f"{platform!r}; falling back to {link!r} constants — calibration "
        f"rows keyed on this link class are folklore until a preset is "
        f"added to costmodel.NET_PRESETS", RuntimeWarning, stacklevel=3)


def derive_link(mesh, axis: str, level: str) -> str:
    """Link-class name for one mesh axis (overridable per Topology).

    Process boundaries classify first: an axis whose devices span multiple
    ``process_index`` values is an *inter* link regardless of platform —
    that is the node-boundary hierarchy the multi-leader algorithms split
    on. Then the platform names the preset:

      * cpu:  cross-process -> "host_ipc", in-process -> "host_cpu"
      * tpu:  keyed on ``device_kind`` through :data:`TPU_LINKS` —
        cross-process/slice -> its DCN preset, else its ICI preset; a kind
        with no row raises (its links were never measured or modeled)
      * anything else: classified the same way from process boundaries but
        mapped onto the host presets, with a once-per-platform warning so
        calibration tables record which rows rest on folklore constants.

    Degenerate size-1 axes carry no traffic and take the intra-class link.
    """
    del level  # boundary walk is what distinguishes levels, not the caller
    try:
        dev0 = mesh.devices.flat[0]
    except (AttributeError, IndexError):
        _warn_fallback("<no devices>", "host_cpu")
        return "host_cpu"
    platform = getattr(dev0, "platform", None) or "<unknown>"
    crossed = _axis_crossings(mesh, axis)
    if platform == "cpu":
        return "host_ipc" if "process_index" in crossed else "host_cpu"
    if platform == "tpu":
        kind = getattr(dev0, "device_kind", None)
        if kind not in TPU_LINKS:
            raise ValueError(f"derive_link: no link presets for TPU device "
                             f"kind {kind!r}; known: {sorted(TPU_LINKS)}")
        ici, dcn = TPU_LINKS[kind]
        return dcn if crossed else ici
    link = "host_ipc" if "process_index" in crossed else "host_cpu"
    _warn_fallback(platform, link)
    return link


@dataclasses.dataclass(frozen=True)
class Topology:
    """A two-level (inter, intra) communication topology.

    Attributes:
      n_nodes: number of groups along the inter ("node") axis.
      n_local: number of devices per group along the intra ("local") axis.
      node_axis: mesh axis name for the inter-group dimension.
      local_axis: mesh axis name for the intra-group dimension.
      node_link: link metadata for the inter level — a NetParams preset name
        or a NetParams instance (None = selector default).
      local_link: link metadata for the intra level, same conventions.
      group: group tag for sub-communicator topologies (empty for the root).
        Set by :meth:`subset` / ``Communicator.split``; it namespaces the
        tuning-table and plan-cache keys so an 8-way TP group and a 2-way DP
        group calibrate and cache independently, while siblings of identical
        shape (same tag) share entries.
    """

    n_nodes: int
    n_local: int
    node_axis: str = "node"
    local_axis: str = "local"
    node_link: Optional[object] = None
    local_link: Optional[object] = None
    group: str = ""

    def __post_init__(self):
        if self.n_nodes < 1 or self.n_local < 1:
            raise ValueError(f"invalid topology {self.n_nodes}x{self.n_local}")

    @property
    def world(self) -> int:
        return self.n_nodes * self.n_local

    @property
    def axes(self) -> Tuple[str, str]:
        return (self.node_axis, self.local_axis)

    @property
    def active_axes(self) -> Tuple[str, ...]:
        """Mesh axes this topology actually communicates over (size > 1).

        Degenerate size-1 levels carry no traffic; dropping them keeps
        sharding specs and collective axis tuples minimal. A fully
        degenerate 1x1 topology still names ``(local_axis,)`` so specs
        stay well-formed.
        """
        sizes = {self.node_axis: self.n_nodes, self.local_axis: self.n_local}
        # dict-keyed to dedupe: a single-axis topology names the same mesh
        # axis at both levels (node_axis == local_axis)
        active = tuple({a: None for a in self.axes if sizes[a] > 1})
        return active or (self.local_axis,)

    @property
    def link_names(self) -> Tuple[str, str]:
        """(inter, intra) link names — stable key material for tuning tables."""
        def name(link, default):
            if link is None:
                return default
            return getattr(link, "name", None) or str(link)
        return (name(self.node_link, "default"),
                name(self.local_link, "default"))

    def with_links(self, node_link=None, local_link=None) -> "Topology":
        """Copy with link metadata filled in (None leaves a field as is)."""
        return dataclasses.replace(
            self,
            node_link=node_link if node_link is not None else self.node_link,
            local_link=(local_link if local_link is not None
                        else self.local_link))

    def flat(self, node: int, local: int) -> int:
        """Flat device index under row-major (node, local) ordering.

        Matches `jax.lax.axis_index((node_axis, local_axis))` semantics.
        """
        return node * self.n_local + local

    @classmethod
    def subset(cls, mesh, axes, parent: Optional["Topology"] = None,
               group: Optional[str] = None) -> "Topology":
        """Derive a sub-communicator Topology from one or two mesh axes.

        One axis -> a flat ``1 x size`` intra-only topology over that axis
        (node level degenerate, so algorithms run their local stage only).
        Two axes -> a full two-level ``(axes[0], axes[1])`` topology.
        Link classes are inherited from ``parent`` when the axis matches one
        of the parent's levels, else auto-derived from the mesh devices.
        ``group`` overrides the group tag (defaults to the joined axis
        names), which namespaces tuning tables and plan caches per group
        shape.
        """
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(axes)
        if not 1 <= len(axes) <= 2:
            raise ValueError(f"subset takes 1 or 2 mesh axes, got {axes!r}")
        for a in axes:
            if a not in mesh.shape:
                raise ValueError(f"axis {a!r} not in mesh axes "
                                 f"{tuple(mesh.axis_names)}")

        def link_for(axis, level):
            if parent is not None:
                if axis == parent.node_axis and parent.node_link is not None:
                    return parent.node_link
                if axis == parent.local_axis and parent.local_link is not None:
                    return parent.local_link
            return derive_link(mesh, axis, level)

        tag = group if group is not None else "x".join(axes)
        if len(axes) == 1:
            (ax,) = axes
            return cls(1, mesh.shape[ax], node_axis=ax, local_axis=ax,
                       node_link=link_for(ax, "intra"),
                       local_link=link_for(ax, "intra"), group=tag)
        node_ax, local_ax = axes
        if node_ax == local_ax:
            raise ValueError(f"duplicate axis {node_ax!r} in subset axes")
        return cls(mesh.shape[node_ax], mesh.shape[local_ax],
                   node_axis=node_ax, local_axis=local_ax,
                   node_link=link_for(node_ax, "inter"),
                   local_link=link_for(local_ax, "intra"), group=tag)

    @classmethod
    def from_mesh(cls, mesh, node_axis: str = "node", local_axis: str = "local",
                  node_link: Optional[object] = None,
                  local_link: Optional[object] = None):
        """Build a Topology from a mesh, auto-deriving link metadata from the
        mesh's devices when not passed explicitly."""
        if node_link is None:
            node_link = derive_link(mesh, node_axis, level="inter")
        if local_link is None:
            local_link = derive_link(mesh, local_axis, level="intra")
        return cls(
            n_nodes=mesh.shape[node_axis],
            n_local=mesh.shape[local_axis],
            node_axis=node_axis,
            local_axis=local_axis,
            node_link=node_link,
            local_link=local_link,
        )
