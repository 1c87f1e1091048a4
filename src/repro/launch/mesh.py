"""Production mesh construction + the Topology modelling its links.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run overrides the
host device count and smoke tests must keep seeing 1 device.

A jax ``Mesh`` only names axes and sizes; the communication model (which
links back the SP axis, at what bandwidth/latency) lives in a
``core.topology.Topology`` built HERE, next to the mesh it describes, so
every consumer — planner, serving engine, roofline, benchmarks — prices
collectives on the same fabric the mesh actually runs on.
"""
from __future__ import annotations

from typing import Optional

from repro.core.topology import Topology


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.  Multi-pod adds a pure-DP
    ``pod`` axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Mesh over all devices with every axis ``Auto``: the executor places
    activations through sharding constraints, which ``jax.make_mesh``'s
    default ``Explicit`` axes would turn into typed shardings."""
    import jax
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def submesh(n_devices: int, data: int = 1, axis_names=("data", "model")):
    """Mesh over the first ``n_devices`` (the elastic-resize survivor set):
    (data, n_devices // data).  Built from an explicit device array so it
    works for any subset size, unlike make_mesh which wants all devices.
    The ONE resize-mesh builder — ``serving.engine.replan`` and
    ``train.trainer.Trainer.replan`` both shrink/regrow through it."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    if n_devices % data:
        raise ValueError(f"{n_devices} devices not divisible by data={data}")
    devs = np.array(jax.devices()[:n_devices]).reshape(
        data, n_devices // data)
    return Mesh(devs, axis_names)


def factorize_sp(topology: Topology):
    """Factor an SP degree into the 2D process grid a hybrid (USP) stage
    runs on: ``(outer, inner)`` with the OUTER (slow, e.g. DCN) axis first
    — ``Topology`` axes are declared outermost-first, so the outer factor
    is the first axis's size and the inner factor the rest.  A single-axis
    fabric has no hybrid factorization and returns ``(1, n)``."""
    if len(topology.axes) < 2:
        return 1, topology.size
    outer = topology.axes[0].size
    return outer, topology.size // outer


def make_sp2d_mesh(outer: int, inner: int, dp: int = 1,
                   dp_axis: str = "data"):
    """Mesh whose SP axis is factorized into a 2D process grid
    ``(sp_out=outer, sp_in=inner)`` — device order keeps the outer (DCN)
    factor MAJOR so each sp_out slice is one host's ICI group.  A hybrid
    stage ring-streams K/V over "sp_out" while a2a-ing inside "sp_in"
    (``core.ulysses.usp_attention``); DSP stages switch over the joint
    ("sp_out", "sp_in") axis pair.  ``dp > 1`` prepends a data axis."""
    if dp > 1:
        return make_mesh((dp, outer, inner), (dp_axis, "sp_out", "sp_in"))
    return make_mesh((outer, inner), ("sp_out", "sp_in"))


def sp2d_topology(outer: int, inner: int, *, placement=None) -> Topology:
    """The fabric of ``make_sp2d_mesh``: ``outer`` hosts of ``inner`` chips
    (DCN outermost) — ``Topology.multihost`` with the same factor order, so
    ``factorize_sp`` round-trips."""
    return Topology.multihost(outer, inner, placement=placement)


def production_topology(*, multi_pod: bool = False) -> Topology:
    """Topology of the production mesh's SP (``model``) axis: 16 chips on
    ICI.  The pod axis is DCN but carries only DP gradient all-reduces, so
    the SP fabric is identical in both configurations."""
    del multi_pod
    return Topology.flat_ici(16)


def mesh_topology(mesh, kind: str = "ici", *,
                  sp_axis: Optional[str] = None,
                  n_hosts: Optional[int] = None) -> Topology:
    """Build the Topology describing ``mesh``'s SP axis.

    ``sp_axis=None`` (the default) auto-detects: the production "model"
    axis when the mesh has one, else the 2D SP process grid
    ("sp_out", "sp_in") of ``make_sp2d_mesh`` — for which the fabric IS the
    grid factorization (outer hosts of inner chips, ``sp2d_topology``), so
    ``kind`` is ignored.  Before this detection a 2D mesh silently priced
    as a size-1 topology (a do-nothing plan).  An explicitly-passed
    ``sp_axis`` missing from the mesh raises instead of mispricing.

    ``kind``:
      "ici"      — every SP link is ICI (single host / pod slice).
      "torus"    — 2D ICI torus over the SP axis (near-square factoring).
      "ici_dcn"  — the SP axis spans ``n_hosts`` hosts (default 2): outer
                   DCN axis x inner per-host ICI axis.
      "uniform"  — the byte model (bandwidth 1, latency 0); plans solved on
                   it match the pre-topology byte-uniform plans exactly.
    """
    if mesh is None:
        return topology_preset(kind, 1, n_hosts=n_hosts)
    if sp_axis is None:
        if "model" in mesh.shape:
            sp_axis = "model"
        elif ("sp_out" in mesh.shape) and ("sp_in" in mesh.shape):
            return sp2d_topology(mesh.shape["sp_out"], mesh.shape["sp_in"])
        else:
            # no recognizable SP axis: a legitimately SP-free (pure-DP)
            # mesh prices as size 1
            return topology_preset(kind, 1, n_hosts=n_hosts)
    elif sp_axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {sp_axis!r} (axes: "
            f"{tuple(mesh.shape)}); refusing to price a size-1 topology "
            f"for an explicitly-named SP axis")
    return topology_preset(kind, mesh.shape[sp_axis], n_hosts=n_hosts)


def topology_preset(kind: str, sp: int, *,
                    n_hosts: Optional[int] = None) -> Topology:
    """Named Topology presets keyed by SP degree (the serve driver's
    ``--topology`` flag resolves through this)."""
    if kind in ("ici", "flat"):
        return Topology.flat_ici(sp)
    if kind == "uniform":
        return Topology.uniform(sp)
    if kind == "torus":
        nx = 1
        for f in range(int(sp ** 0.5), 0, -1):
            if sp % f == 0:
                nx = f
                break
        return Topology.torus_2d(nx, sp // nx)
    if kind == "ici_dcn":
        hosts = n_hosts or 2
        if sp % hosts:
            raise ValueError(f"SP degree {sp} not divisible by "
                             f"{hosts} hosts")
        return Topology.multihost(hosts, sp // hosts)
    raise ValueError(f"unknown topology kind {kind!r} "
                     "(want ici|torus|ici_dcn|uniform)")


def resolve_topology(kind: str, sp: int, *,
                     n_hosts: Optional[int] = None) -> Topology:
    """Named preset, or ``profile:<path>`` — a JSON file of
    ``[[global_bytes, seconds], ...]`` all-gather samples fitted by
    ``Topology.from_profile`` so a MEASURED fabric prices the plan.  Shared
    by the serve driver (``--topology``) and the dry-run
    (``launch/dryrun.py --topology``, which records the fitted fabric in
    the cell metas)."""
    if kind.startswith("profile:"):
        import json
        with open(kind[len("profile:"):]) as f:
            samples = [tuple(s) for s in json.load(f)]
        return Topology.from_profile(sp, samples)
    return topology_preset(kind, sp, n_hosts=n_hosts)


def topology_meta(topo: Optional[Topology]) -> dict:
    """The fabric facts a meta/metrics JSON records for a Topology: the
    per-link model the planner priced on."""
    if topo is None:
        return {"topology": None}
    return {
        "topology": [{"name": a.name, "size": a.size,
                      "bandwidth_gbps": a.bandwidth / 1e9,
                      "latency_s": a.latency} for a in topo.axes],
        "bottleneck_bandwidth_gbps": topo.bottleneck_bandwidth / 1e9,
    }
