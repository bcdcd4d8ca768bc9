"""Distributed BARQ: hash-partitioned join + GROUP BY across 8 (placeholder)
devices via shard_map — the multi-pod execution path of DESIGN.md §2.1.

    PYTHONPATH=src python examples/distributed_join.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import collections  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.data import generate_social_graph  # noqa: E402


def main():
    compile_cache.enable()
    print(f"devices: {len(jax.devices())}")
    store, meta = generate_social_graph(scale=0.3)
    print(f"social graph: {meta}")

    # relation 1: (?p1 :knows ?p2) ; relation 2: (?p2 :hasInterest ?tag)
    d = store.dict
    spoc = store.index_array("spoc")
    knows = spoc[spoc[:, 1] == d.lookup(":knows")]
    interest = spoc[spoc[:, 1] == d.lookup(":hasInterest")]
    # join on ?p2: left keyed by object (p2), right keyed by subject
    left = np.stack([knows[:, 2], knows[:, 0]]).astype(np.int32)
    right = np.stack([interest[:, 0], interest[:, 2]]).astype(np.int32)
    print(f"|knows|={left.shape[1]} |interest|={right.shape[1]}")

    mesh = D.engine_mesh()
    join_count = D.make_join_count(mesh, cap_factor=4.0)
    l_sh = D.shard_relation(mesh, left)
    r_sh = D.shard_relation(mesh, right)

    t0 = time.perf_counter()
    count, overflow = join_count(l_sh, r_sh)
    jax.block_until_ready(count)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    count, overflow = join_count(l_sh, r_sh)
    jax.block_until_ready(count)
    t_steady = time.perf_counter() - t0

    lc = collections.Counter(left[0].tolist())
    rc = collections.Counter(right[0].tolist())
    oracle = sum(lc[k] * rc[k] for k in lc if k in rc)
    print(f"distributed join count = {int(count)} (oracle {oracle}) "
          f"overflow={int(overflow)}")
    assert int(count) == oracle and int(overflow) == 0
    print(f"compile+run: {t_first:.3f}s, steady-state: {t_steady * 1e3:.1f}ms")

    # distributed GROUP BY ?p2 COUNT(*) over the knows relation
    group = D.make_group_count(mesh, cap_factor=4.0, max_groups_per_dev=4096)
    gkeys, gcounts, of = group(l_sh)
    gk, gc = np.asarray(gkeys).ravel(), np.asarray(gcounts).ravel()
    valid = gk != np.iinfo(np.int32).max
    got = {int(k): int(c) for k, c in zip(gk[valid], gc[valid]) if c > 0}
    assert got == dict(lc)
    print(f"distributed group-count over {len(got)} groups matches oracle ✓")


if __name__ == "__main__":
    main()
