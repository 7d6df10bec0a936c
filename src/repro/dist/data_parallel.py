"""Manual data parallelism with pluggable gradient-reduction schedules.

``make_dp_grad_fn`` wraps a ``loss_fn(params, batch) -> (loss, aux)`` into
a shard_map over the mesh's batch axes: the batch splits across
("pod", "data"), each shard runs value_and_grad locally, and gradients are
combined by one of:

    flat      — one fused psum over ("pod", "data") (GSPMD's default)
    hier      — reduce-scatter in-pod, psum across pods, all-gather back
                (``collectives.hierarchical_psum``)
    hier+int8 — the pod hop additionally int8-compressed
                (``compression.compressed_psum``)

All schedules return the same (loss, grads) up to float reassociation
(int8 adds bounded quantization error on the pod hop only); the dry-run's
HLO collective census measures what each schedule moves across the pod
boundary.
"""
from __future__ import annotations

from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import context
from repro.dist import collectives, compression, sharding

SCHEDULES = ("flat", "hier")


def make_dp_grad_fn(loss_fn: Callable, mesh, *, schedule: str = "flat",
                    compress: bool = False) -> Callable:
    """Return ``fn(params, batch) -> (loss, grads)`` (see module docstring).

    ``loss_fn`` must return ``(loss, aux)``; the mean loss and mean
    gradients over the global batch are returned.  On a mesh without
    batch axes this degenerates to plain ``value_and_grad`` — the
    single-device fallback.
    """
    assert schedule in SCHEDULES, schedule
    assert not compress or schedule == "hier", \
        "compress rides the hierarchical schedule (int8 on the pod hop)"
    dp_axes = context.data_axes(mesh)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    if not dp_axes:
        def fallback(params, batch):
            (loss, _aux), grads = grad_fn(params, batch)
            return loss, grads
        return fallback

    n_total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    outer, inner = dp_axes[0], dp_axes[1:]

    def reduce_grads(g):
        if compress:
            # exact psum on the fast inner axes, int8 on the pod hop
            if inner:
                g = jax.tree.map(lambda t: jax.lax.psum(t, inner), g)
            g = jax.tree.map(
                lambda t: compression.compressed_psum(t, outer)
                .astype(t.dtype), g)
        elif schedule == "hier" and inner:
            g = collectives.hierarchical_psum_tree(g, dp_axes)
        else:
            g = jax.tree.map(lambda t: jax.lax.psum(t, dp_axes), g)
        return jax.tree.map(lambda t: t / n_total, g)

    def shard_fn(params, batch):
        # the body is a *manual* region: hide the ambient mesh so model
        # code does not emit nested GSPMD sharding constraints
        with context.suspend_mesh():
            (loss, _aux), grads = grad_fn(params, batch)
        loss = jax.lax.psum(loss, dp_axes) / n_total
        return loss, reduce_grads(grads)

    def fn(params, batch):
        b = jax.tree.leaves(batch)[0].shape[0]
        entry = sharding.batch_entry(mesh, b)
        batch_specs = jax.tree.map(lambda _: P(entry), batch)
        # check_vma off: the bodies' psum_scatter/ppermute patterns are
        # not replication-typed.
        mapped = jax.shard_map(shard_fn, mesh=mesh,
                               in_specs=(P(), batch_specs),
                               out_specs=(P(), P()), check_vma=False)
        return mapped(params, batch)

    return fn
