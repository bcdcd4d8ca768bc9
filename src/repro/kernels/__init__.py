"""Pallas TPU kernels for the engine's compute hot spots (DESIGN.md §2):

    join_expand      — merge-join Build-phase cross-product materialization
    sorted_search    — vectorized binary search (batched skip()/seek)
    segment_reduce   — segmented scan for streaming aggregation
    expr_eval        — fused expression-VM program evaluation (§9)
    frontier_dedup   — property-path BFS delta-frontier masks
    gather_emit      — fused join emission (gather + NULL-extend + keys)
    radix_partition  — distributed-exchange partitioning
    hash_join        — hash-join probe over a radix-partitioned build
    bloom_filter     — SIP prefilter build + probe

``repro.kernels.ops`` dispatches numpy / jnp-ref / pallas backends, the
platform picking the default; ``repro.kernels.ref`` holds the pure-jnp
oracles. Each kernel module exposes a jitted ``*_kernel`` device entry over
tile-aligned inputs and a host ``*_pallas`` wrapper that pads to
power-of-two buckets (``tiling``).
"""
