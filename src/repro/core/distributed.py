"""Distributed BARQ: partitioned joins/aggregation via shard_map (beyond
paper — the multi-pod posture of DESIGN.md §2.1/§5).

Stardog's BARQ is single-node; scaling the same vectorized operators to a
TPU pod follows the classic Volcano exchange-operator recipe (the paper
cites Graefe [8] for exactly this): hash-partition both relations on the
join key (radix_partition kernel), exchange buckets with one all_to_all,
then run the *local* vectorized merge join per device. Keys are co-located
after the exchange, so local results concatenate to the global result;
COUNT-style queries reduce with one psum.

Everything here is static-shape: per-device bucket capacity is
ceil(n_local/P)*slack, rows beyond capacity are counted in an overflow
counter (monitoring surfaces it; production would re-run with higher
slack — same contract as MoE capacity dropping).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_SENTINEL = jnp.iinfo(jnp.int32).max
_HASH_MULT = np.uint32(0x9E3779B1)

AXIS = "shard"


def engine_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (AXIS,))


# ---------------------------------------------------------------------------
# exchange
# ---------------------------------------------------------------------------


def _exchange(rows: jax.Array, keys: jax.Array, n_parts: int, cap: int):
    """Inside shard_map: route rows to the device owning hash(key).

    rows: (C, n_local) int32; keys: (n_local,). Returns (C, n_parts*cap)
    received rows (padded with sentinel keys) + overflow count.
    """
    n_local = keys.shape[0]
    h = (keys.astype(jnp.uint32) * _HASH_MULT) >> np.uint32(16)
    pid = (h & np.uint32(n_parts - 1)).astype(jnp.int32)

    order = jnp.argsort(pid)
    pid_s = pid[order]
    rows_s = rows[:, order]
    keys_s = keys[order]

    # position of each row within its bucket
    start = jnp.searchsorted(pid_s, jnp.arange(n_parts, dtype=jnp.int32), side="left")
    within = jnp.arange(n_local, dtype=jnp.int32) - start[pid_s]
    ok = within < cap
    overflow = jnp.sum(~ok)

    buf_keys = jnp.full((n_parts, cap), _SENTINEL, jnp.int32)
    buf_rows = jnp.full((rows.shape[0], n_parts, cap), _SENTINEL, jnp.int32)
    iw = jnp.where(ok, within, cap - 1)  # clamp; overflow rows overwritten last
    buf_keys = buf_keys.at[pid_s, iw].set(jnp.where(ok, keys_s, _SENTINEL))
    buf_rows = buf_rows.at[:, pid_s, iw].set(
        jnp.where(ok[None, :], rows_s, _SENTINEL)
    )

    recv_keys = jax.lax.all_to_all(buf_keys, AXIS, 0, 0, tiled=False)
    recv_rows = jax.lax.all_to_all(buf_rows, AXIS, 1, 1, tiled=False)
    return (
        recv_rows.reshape(rows.shape[0], -1),
        recv_keys.reshape(-1),
        overflow,
    )


def _local_sorted(keys: jax.Array, rows: jax.Array):
    order = jnp.argsort(keys)  # sentinels sort to the end
    return keys[order], rows[:, order]


# ---------------------------------------------------------------------------
# distributed join (count + materialized-capacity forms)
# ---------------------------------------------------------------------------


def _join_count_local(lkeys, rkeys) -> jax.Array:
    """#matches of the sorted local shards (sentinel-padded)."""
    lo = jnp.searchsorted(rkeys, lkeys, side="left")
    hi = jnp.searchsorted(rkeys, lkeys, side="right")
    valid = lkeys != _SENTINEL
    return jnp.sum(jnp.where(valid, hi - lo, 0).astype(jnp.int32))


def make_join_count(mesh: Mesh, cap_factor: float = 2.0):
    """Returns jitted f(left_rows, right_rows, lkey_idx, rkey_idx) -> (count,
    overflow). Inputs are (C, N) int32 relations sharded on axis 1."""
    n_parts = mesh.devices.size

    def local(lrows, rrows):
        lkeys = lrows[0]
        rkeys = rrows[0]
        lcap = int(np.ceil(lkeys.shape[0] * cap_factor / n_parts))
        rcap = int(np.ceil(rkeys.shape[0] * cap_factor / n_parts))
        lrows2, lkeys2, lof = _exchange(lrows, lkeys, n_parts, lcap)
        rrows2, rkeys2, rof = _exchange(rrows, rkeys, n_parts, rcap)
        lkeys3, _ = _local_sorted(lkeys2, lrows2)
        rkeys3, _ = _local_sorted(rkeys2, rrows2)
        cnt = _join_count_local(lkeys3, rkeys3)
        total = jax.lax.psum(cnt, AXIS)
        of = jax.lax.psum(lof + rof, AXIS)
        return total, of

    shmapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, AXIS), P(None, AXIS)),
        out_specs=(P(), P()),
    )
    return jax.jit(shmapped)


def make_join_materialize(mesh: Mesh, out_cap_per_device: int, cap_factor: float = 2.0):
    """Materializing variant: returns per-device joined key column + left/
    right payload row indices up to a static capacity (overflow counted).
    Output: (keys (P*cap,), n_valid per device summed, overflow)."""
    n_parts = mesh.devices.size
    out_cap = out_cap_per_device

    def local(lrows, rrows):
        lkeys_raw = lrows[0]
        rkeys_raw = rrows[0]
        lcap = int(np.ceil(lkeys_raw.shape[0] * cap_factor / n_parts))
        rcap = int(np.ceil(rkeys_raw.shape[0] * cap_factor / n_parts))
        lrows2, lkeys2, lof = _exchange(lrows, lkeys_raw, n_parts, lcap)
        rrows2, rkeys2, rof = _exchange(rrows, rkeys_raw, n_parts, rcap)
        lkeys, lrows3 = _local_sorted(lkeys2, lrows2)
        rkeys, rrows3 = _local_sorted(rkeys2, rrows2)

        lo = jnp.searchsorted(rkeys, lkeys, side="left")
        hi = jnp.searchsorted(rkeys, lkeys, side="right")
        valid = lkeys != _SENTINEL
        counts = jnp.where(valid, hi - lo, 0)
        cum = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)]).astype(
            jnp.int32
        )
        total = cum[-1]
        # expand to out_cap slots (join_expand ref semantics)
        t = jnp.arange(out_cap, dtype=jnp.int32)
        g = jnp.clip(jnp.searchsorted(cum, t, side="right") - 1, 0, lkeys.shape[0] - 1)
        w = t - cum[g]
        li = g
        ri = lo[g] + w
        ok = t < total
        out_keys = jnp.where(ok, lkeys[li], _SENTINEL)
        out_li = jnp.where(ok, li, -1)
        out_ri = jnp.where(ok, ri, -1)
        of = jax.lax.psum(lof + rof + jnp.maximum(total - out_cap, 0), AXIS)
        n = jax.lax.psum(jnp.minimum(total, out_cap).astype(jnp.int32), AXIS)
        return out_keys, out_li, out_ri, n, of

    shmapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, AXIS), P(None, AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P()),
    )
    return jax.jit(shmapped)


def make_group_count(mesh: Mesh, cap_factor: float = 2.0, max_groups_per_dev: int = 1 << 16):
    """Distributed GROUP BY key COUNT(*): exchange by key hash, local sorted
    segment counts. Keys are co-located, so local runs are globally correct.
    Returns per-device (keys, counts) padded to max_groups_per_dev."""
    n_parts = mesh.devices.size

    def local(rows):
        keys_raw = rows[0]
        cap = int(np.ceil(keys_raw.shape[0] * cap_factor / n_parts))
        _, keys2, of = _exchange(rows, keys_raw, n_parts, cap)
        keys = jnp.sort(keys2)
        valid = keys != _SENTINEL
        is_start = jnp.concatenate(
            [valid[:1], (keys[1:] != keys[:-1]) & valid[1:]]
        )
        gid = jnp.cumsum(is_start.astype(jnp.int32)) - 1
        counts = jax.ops.segment_sum(
            valid.astype(jnp.int32), jnp.where(valid, gid, max_groups_per_dev - 1),
            num_segments=max_groups_per_dev,
        )
        first_pos = jnp.where(
            is_start, jnp.arange(keys.shape[0], dtype=jnp.int32), keys.shape[0] - 1
        )
        starts = jnp.concatenate(
            [
                jnp.sort(jnp.where(is_start, first_pos, jnp.iinfo(jnp.int32).max)),
                jnp.full((max_groups_per_dev,), jnp.iinfo(jnp.int32).max, jnp.int32),
            ]
        )[:max_groups_per_dev]
        gkeys = jnp.where(
            starts < keys.shape[0], keys[jnp.clip(starts, 0, keys.shape[0] - 1)], _SENTINEL
        )
        return gkeys, counts, jax.lax.psum(of, AXIS)

    shmapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, AXIS),),
        out_specs=(P(AXIS), P(AXIS), P()),
    )
    return jax.jit(shmapped)


# ---------------------------------------------------------------------------
# host-side convenience for tests / examples
# ---------------------------------------------------------------------------


def shard_relation(mesh: Mesh, rows: np.ndarray) -> jax.Array:
    """Pad a (C, N) relation to the mesh size and device_put it sharded."""
    n_dev = mesh.devices.size
    c, n = rows.shape
    n_pad = int(np.ceil(max(n, 1) / n_dev) * n_dev)
    out = np.full((c, n_pad), _SENTINEL, dtype=np.int32)
    out[:, :n] = rows
    return jax.device_put(out, NamedSharding(mesh, P(None, AXIS)))
