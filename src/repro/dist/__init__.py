"""Distribution layer: device meshes, sharding rules, and collectives.

This package maps model computation onto a ``jax.sharding.Mesh`` — the
software analogue of the Ring-Mesh interconnect hierarchy (DESIGN.md §9):
the ``model`` mesh axis plays the role of a ringlet (tight, high-bandwidth
neighborhood), ``data`` the global mesh, and ``pod`` the expensive
pod-boundary hop whose traffic the hierarchical/compressed collectives
shape.

Modules:
    context       — ambient mesh registry (``use_mesh`` / ``current_mesh``)
    sharding      — logical axes -> mesh axes (``fit_spec`` divisibility
                    fallback, param/batch/cache PartitionSpecs)
    collectives   — hierarchical all-reduce (reduce-scatter in-pod, psum
                    across pods, all-gather back)
    compression   — int8 quantization + error feedback, compressed psum
    data_parallel — manual-DP gradient functions (flat / hier / int8 pod hop)
    decode_attn   — sequence-sharded decode attention over a ppermute ring
"""

__all__ = ["context", "sharding", "collectives", "compression",
           "data_parallel", "decode_attn"]
