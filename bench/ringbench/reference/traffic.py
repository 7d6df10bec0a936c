"""The traffic a point offers, drawn as the configuration's model states.

Statistical traffic: each cycle every PE injects one single-flit packet with
probability ``inj_rate``.  Its destination is, with probability
``locality_ringlet``, another PE of its own 4-PE group; else with
probability ``locality_block`` another PE of its own 16-PE group; else the
pattern's fixed destination, or for ``uniform`` any other PE.

The random streams are JAX's counter-based ``threefry`` draws from
``PRNGKey(seed)``, split five ways in the order: injection, uniform
offset, locality draw, ringlet offset, block offset.  A point with faults
splits six ways; the sixth key draws the drop chances of transient
faults, which no traffic here has.  They are drawn on the host CPU; the
draws are the same on every backend.
"""
from __future__ import annotations

import jax
import numpy as np

from ringbench.reference import fabric as fb


def _bits(n_pes: int) -> int:
    bits = n_pes.bit_length() - 1
    if 1 << bits != n_pes:
        raise ValueError(f"the pattern needs a power-of-two PE count, "
                         f"got {n_pes}")
    return bits


def pattern_destinations(pattern: str, n_pes: int):
    """A PE's fixed destination under ``pattern``, or None for uniform."""
    src = np.arange(n_pes, dtype=np.int64)
    if pattern == "uniform":
        return None
    if pattern == "bit_reversal":
        bits = _bits(n_pes)
        return sum(((src >> i) & 1) << (bits - 1 - i) for i in range(bits))
    if pattern == "transpose":
        bits = _bits(n_pes)
        half = bits // 2
        return ((src << half) | (src >> (bits - half))) & (n_pes - 1)
    raise ValueError(f"the reference has no pattern {pattern!r}")


def draws(seed: int, cycles: int, n_pes: int, inj_rate: float,
          faulted: bool) -> dict:
    """The point's random streams as host arrays."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(np.int32(seed))
        keys = jax.random.split(key, 6 if faulted else 5)
        shape = (cycles, n_pes)
        out = {
            "inj": jax.random.bernoulli(keys[0], np.float32(inj_rate), shape),
            "offset": jax.random.randint(keys[1], shape, 1, n_pes,
                                         dtype=np.int32),
            "u": jax.random.uniform(keys[2], shape),
            "ring": jax.random.randint(keys[3], shape, 1, fb.PES_PER_RINGLET,
                                       dtype=np.int32),
            "block": jax.random.randint(keys[4], shape, 1, fb.PES_PER_BLOCK,
                                        dtype=np.int32),
        }
        return {k: np.asarray(v) for k, v in out.items()}


def destinations(d: dict, n_pes: int, pattern: str, loc_ringlet: float,
                 loc_block: float) -> np.ndarray:
    """``[cycles, P]`` destination of each draw (float32 thresholds, as
    stated in float32 by the point)."""
    pes = np.arange(n_pes, dtype=np.int64)
    fixed = pattern_destinations(pattern, n_pes)
    base = ((pes + d["offset"]) % n_pes if fixed is None
            else np.broadcast_to(fixed, d["offset"].shape))
    rp, bp = fb.PES_PER_RINGLET, fb.PES_PER_BLOCK
    ring_peer = pes - pes % rp + (pes % rp + d["ring"]) % rp
    block_peer = pes - pes % bp + (pes % bp + d["block"]) % bp
    lr, lb = np.float32(loc_ringlet), np.float32(loc_block)
    return np.where(d["u"] < lr, ring_peer,
                    np.where(d["u"] < lr + lb, block_peer, base))
