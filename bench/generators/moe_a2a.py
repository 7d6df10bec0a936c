"""DeepSeek-V3's routed-expert dispatch and combine as trace traffic on a
ring-mesh (``bench/traffic/moe_a2a.json`` holds the published values).

Expert ``e`` lives on ringlet ``e`` (PEs ``4e..4e+3``); expert group ``g``
is router row ``g`` (ringlet ``r`` hangs off block ``r // 4``, in row
``r // 4 // blocks_x``).  Each PE holds ``tokens_per_pe`` tokens.  Per
draw, every (token, expert) pair gets ``u ~ N(0, 1)`` and the affinity
``s = sigmoid(u)``; a group scores the sum of its two highest ``s``; the
router keeps the ``topk_group`` best groups and takes the
``num_experts_per_tok`` highest ``s`` among them, ties to the lower id.
Token ``t`` of PE ``p`` goes to PE ``4e + p % 4`` of each chosen expert
``e``: the expert's batch is split over its ringlet by the sender's
ringlet position.  A pair whose target is ``p`` itself stays local and has no record.

Two phases, behind the default barrier: dispatch, one record ``(p, 4e +
p % 4, 1)`` per pair in (PE, token, expert) order; combine, one record
``(4e + p % 4, p, 2)`` per pair, each source's rows in (owner PE, token,
expert) order.  A record's flit stands for ``scale`` flits of
``flit_bytes``: at the scale ``hidden x 1 B / flit_bytes`` a dispatch pair
(``hidden`` FP8 values) is 1 flit and a combine pair (BF16) 2.

On a smaller fabric (``small.shrink``): experts = ringlets, groups =
router rows, kept groups = max(1, groups // 2), experts per token =
min(``num_experts_per_tok``, half the experts in the kept groups).  At
``n_routed_experts`` ringlets that rule must give the published router.
"""
from __future__ import annotations

import math

import numpy as np

from ringbench.reference import fabric as fb


def router(config: dict, mix: dict) -> tuple[int, int, int, int]:
    """``(experts, groups, kept groups, experts per token)`` on the
    configuration's fabric."""
    if config["family"] != "ring_mesh":
        raise ValueError(f"experts live on ringlets: {config['family']} "
                         f"has none")
    if mix["scoring_func"] != "sigmoid":
        raise ValueError(f"scoring_func {mix['scoring_func']!r}")
    experts = config["n_pes"] // fb.PES_PER_RINGLET
    groups = config["blocks_y"]
    per_group = fb.PES_PER_BLOCK // fb.PES_PER_RINGLET * config["blocks_x"]
    if groups * per_group != experts:
        raise ValueError(f"{experts} ringlets are not {groups} rows of "
                         f"{per_group}")
    kept = max(1, groups // 2)
    k = min(mix["num_experts_per_tok"], kept * per_group // 2)
    published = (mix["n_routed_experts"], mix["n_group"], mix["topk_group"],
                 mix["num_experts_per_tok"])
    if experts >= mix["n_routed_experts"] and (experts, groups, kept,
                                               k) != published:
        raise ValueError(f"the fabric maps the router as {experts} experts, "
                         f"{groups} groups, top {kept} groups, top {k} "
                         f"experts, not {published}")
    return experts, groups, kept, k


def choose(s: np.ndarray, groups: int, kept: int, k: int) -> np.ndarray:
    """Each token's chosen experts ``[tokens, k]``, ascending, from its
    affinities ``s [tokens, experts]``."""
    n_tok, n_exp = s.shape
    by_group = s.reshape(n_tok, groups, n_exp // groups)
    group_score = np.sort(by_group, axis=2)[:, :, -2:].sum(axis=2)
    top_groups = np.argsort(-group_score, axis=1, kind="stable")[:, :kept]
    in_kept = np.zeros((n_tok, groups), bool)
    np.put_along_axis(in_kept, top_groups, True, axis=1)
    masked = np.where(np.repeat(in_kept, n_exp // groups, axis=1), s, -1.0)
    return np.sort(np.argsort(-masked, axis=1, kind="stable")[:, :k], axis=1)


def traffics(config: dict, mix: dict, rng: np.random.Generator) -> list:
    experts, groups, kept, k = router(config, mix)
    n_pes, per_pe = config["n_pes"], mix["tokens_per_pe"]
    flit_bytes, hidden = mix["flit_bytes"], mix["hidden_size"]
    scale = hidden * mix["dispatch_bytes_per_value"] / flit_bytes
    go, back = (math.ceil(hidden * mix[key] / (flit_bytes * scale))
                for key in ("dispatch_bytes_per_value",
                            "combine_bytes_per_value"))
    src = np.repeat(np.arange(n_pes), per_pe * k)
    out = []
    for i in range(mix["draws"]):
        u = rng.standard_normal((n_pes * per_pe, experts))
        chosen = choose(1.0 / (1.0 + np.exp(-u)), groups, kept, k)
        dst = (fb.PES_PER_RINGLET * chosen.reshape(-1)
               + src % fb.PES_PER_RINGLET)
        remote = dst != src
        s, d = src[remote], dst[remote]
        order = np.argsort(d, kind="stable")
        out.append({
            "schedule": f"draw{i}", "flit_bytes": flit_bytes,
            "scale": scale,
            "phases": [
                np.stack([s, d, np.full(s.size, go)], axis=1),
                np.stack([d[order], s[order], np.full(s.size, back)],
                         axis=1)]})
    return out
