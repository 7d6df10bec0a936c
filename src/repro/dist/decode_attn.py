"""Sequence-sharded decode attention over a ppermute ring.

Long-context decode is KV-bound: a 512k cache does not fit one device, and
head-sharding dies when the head count does not divide the ``model`` axis
(6-head GQA on an 8-wide axis).  So the *sequence* dimension of the cache
shards over ``model`` and the (tiny) query visits every shard via
``jax.lax.ppermute`` ring steps — the software analogue of the paper's
ring transfers: each step moves one KV chunk to the neighbor while every
device consumes the chunk it holds (flash-decoding / ring-attention).

Per ring step the device folds its current chunk into a streaming-softmax
accumulator (running max ``m``, normalizer ``l``, weighted value sum), so
the result is exact — identical to ``kernels.ref.attention_ref`` — while
no device ever materializes more than ``S / n_shards`` keys.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import context

_NEG = -1e30  # finite mask value: keeps the streaming max NaN-free


def _ring_attention(q, k, v, off, *, axis: str, n: int, chunk: int,
                    skv: int, causal: bool, window: Optional[int],
                    scale: float):
    """shard_map body: q (b,Hq,Sq,D) replicated over ``axis``; k/v local
    chunks (b,Hkv,chunk,D).  ``off`` is the absolute position of q[0].

    GQA stays grouped throughout: the ring moves the *raw* Hkv-head
    chunks (never the group-repeated tensors), so each step transfers
    exactly S/n keys' worth of bytes."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kk = k.astype(jnp.float32)
    vv = v.astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(b, hkv, group, sq, d)
    q_pos = off + jnp.arange(sq)

    i = jax.lax.axis_index(axis)
    m = jnp.full((b, hkv, group, sq), _NEG, jnp.float32)
    l = jnp.zeros((b, hkv, group, sq), jnp.float32)
    acc = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    for step in range(n):
        # after `step` rotations, we hold the chunk owned by rank i - step
        owner = (i - step) % n
        k_pos = owner * chunk + jnp.arange(chunk)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kk) * scale
        mask = (k_pos < skv)[None, :]                    # padding tail
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        mask = mask[None, None, None]                    # (1,1,1,Sq,chunk)
        smax = jnp.max(jnp.where(mask, s, _NEG), axis=-1)
        m_new = jnp.maximum(m, smax)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] \
            + jnp.einsum("bhgqk,bhkd->bhgqd", p, vv)
        m = m_new
        if step < n - 1:
            kk = jax.lax.ppermute(kk, axis, perm)
            vv = jax.lax.ppermute(vv, axis, perm)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def seq_sharded_attention(q, k, v, *, causal: bool = True,
                          window: Optional[int] = None, q_offset=None,
                          scale: Optional[float] = None,
                          seq_axis: str = "model"):
    """Decode attention with the KV sequence sharded over ``seq_axis``.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D), Hq % Hkv == 0.  Matches
    ``kernels.ref.attention_ref`` semantics (causal / sliding window /
    ``q_offset`` into a fixed cache buffer; may be a traced scalar).

    Without an ambient mesh — or when the mesh lacks ``seq_axis`` — this
    falls back to the single-device reference path, so callers never need
    to special-case the unsharded world.
    """
    mesh = context.current_mesh()
    if mesh is None or seq_axis not in mesh.axis_names \
            or int(mesh.shape[seq_axis]) <= 1:
        from repro.kernels import ref
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 scale=scale, q_offset=q_offset)

    n = int(mesh.shape[seq_axis])
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    off = jnp.asarray(skv - sq if q_offset is None else q_offset, jnp.int32)

    pad = (-skv) % n
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    chunk = (skv + pad) // n

    from repro.dist import sharding
    bentry = sharding.batch_entry(mesh, b)
    qspec = P(bentry, None, None, None)
    kvspec = P(bentry, None, seq_axis, None)

    def body(qb, kb, vb, offb):
        return _ring_attention(qb, kb, vb, offb, axis=seq_axis, n=n,
                               chunk=chunk, skv=skv, causal=causal,
                               window=window, scale=scale)

    mapped = jax.shard_map(body, mesh=mesh,
                           in_specs=(qspec, kvspec, kvspec, P()),
                           out_specs=qspec, check_vma=False)
    return mapped(q, k, v, off)
