"""Hierarchical collectives (the Ring-Mesh reduction schedule in software).

A flat ``psum`` over ("pod", "data") moves the full gradient across the
pod boundary.  The hierarchical schedule mirrors the paper's
ring-then-mesh traffic shaping:

    1. reduce-scatter inside each pod (over the fast inner axes) — every
       device ends up owning 1/N_inner of the reduction;
    2. all-reduce only that shard across pods (the expensive hop moves
       1/N_inner of the bytes);
    3. all-gather inside each pod to restore the full tensor.

The result equals the flat psum up to float reassociation.  All functions
are written for use *inside* ``shard_map`` bodies over mapped axes.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def hierarchical_psum(x, axes: tuple[str, ...] = ("pod", "data")):
    """All-reduce ``x`` over ``axes`` with the hierarchical schedule.

    ``axes[0]`` is the outer (pod-boundary) axis; the remaining axes are
    the intra-pod axes used for the reduce-scatter/all-gather phases.
    With a single axis this degenerates to a plain psum.
    """
    axes = tuple(axes)
    if len(axes) == 1:
        return jax.lax.psum(x, axes[0])
    outer, inner = axes[0], axes[1:]
    n_inner = int(np.prod([jax.lax.axis_size(a) for a in inner]))
    flat = x.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % n_inner
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = flat
    for a in inner:
        shard = jax.lax.psum_scatter(shard, a, scatter_dimension=0,
                                     tiled=True)
    shard = jax.lax.psum(shard, outer)
    for a in reversed(inner):
        shard = jax.lax.all_gather(shard, a, axis=0, tiled=True)
    return shard[:size].reshape(x.shape)


def hierarchical_psum_tree(tree, axes: tuple[str, ...] = ("pod", "data")):
    """``hierarchical_psum`` over every leaf of a pytree."""
    return jax.tree.map(lambda t: hierarchical_psum(t, axes), tree)
