"""Mesh and shard_map construction for the distributed layer, in one place.

Everything that builds a mesh or wraps a function in ``jax.shard_map`` goes
through this module, so the rest of ``repro.dist`` (and the subprocess test
programs) share one spelling: meshes with ``Auto`` axis types, and
``shard_map`` with the replication check off by default.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; call sites pass ``check_vma=False``.

    The FFT layer uses ``axis_index``-dependent twiddles, which the
    replication checker cannot prove anything useful about.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=(AxisType.Auto,) * len(axis_names)
    )


def make_hier_mesh(data: int, host: int, device: int):
    """``data x host x device`` mesh for the hierarchical two-stage transpose.

    The transform axis of ``repro.dist.fft`` factors over the
    ``("host", "device")`` pair (p = host * device, device-major sharding —
    see ``fft.shard_axes``); a leading batch of signals shards over
    ``"data"`` exactly as on a flat mesh.  Axis order follows jax's
    convention that later mesh axes are nearer neighbors: the device tier
    (fast ICI) is innermost, hosts (slow DCN) outside it, so the
    ``host * device`` consecutive devices of one data slice group into
    ``host`` contiguous fast-tier islands.
    """
    return make_mesh((data, host, device), ("data", "host", "device"))
