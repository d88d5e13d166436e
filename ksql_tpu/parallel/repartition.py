"""ICI all-to-all repartition — the repartition-topic replacement.

In the reference, GROUP BY / PARTITION BY with a new key writes every record
to an internal repartition topic and reads it back through the broker
(StreamGroupByBuilderBase.java:39, PartitionByParamsFactory) — a network
round-trip per shuffle.  Here the shuffle is a single XLA all-to-all over
ICI inside ``shard_map``: rows are bucketed by destination shard
(``hash mod n_shards``) into fixed-capacity per-destination lanes, exchanged
in one collective, and land on the device that owns their key's state shard.

Static shapes: each (src, dst) bucket has fixed ``bucket_capacity`` lanes;
rows that overflow a bucket are counted (``overflow``) rather than silently
dropped — the host reacts by lowering batch fill or raising capacity, the
moral analog of broker backpressure.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ksql_tpu.parallel.mesh import SHARD_AXIS


def shard_of(khash: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Destination shard for each row.  Uses high bits so the store's slot
    probing (low bits of a different mix) stays decorrelated."""
    u = jax.lax.shift_right_logical(khash, 40)
    return (u % n_shards).astype(jnp.int32)


def np_shard_of(khash, n_shards: int):
    """Host (numpy) replica of :func:`shard_of` — must stay bit-identical;
    used by checkpoint reshard-on-restore to re-partition saved store rows
    under a different mesh size."""
    import numpy as np

    u = np.asarray(khash, np.int64).view(np.uint64) >> np.uint64(40)
    return (u % np.uint64(n_shards)).astype(np.int64)


@jax.named_scope("exchange")
def all_to_all_exchange(
    payload: Dict[str, jnp.ndarray],
    dest: jnp.ndarray,
    n_shards: int,
    bucket_capacity: int,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Exchange a per-row payload so each row lands on shard ``dest[row]``.

    Must be called inside shard_map over the ``shards`` axis.  Input arrays
    are the local [n] rows; outputs are the local
    [n_shards * bucket_capacity] received rows.  ``payload['active']`` marks
    live lanes in and out.  Returns (received payload, overflow count).
    """
    active = payload["active"]
    n = active.shape[0]
    cap = bucket_capacity
    total = n_shards * cap
    trash = jnp.int32(total)  # scatter sink for inactive/overflowed rows
    target = jnp.full(n, trash, jnp.int32)
    overflow = jnp.zeros((), jnp.int64)
    for d in range(n_shards):
        mask = active & (dest == d)
        idx = jnp.cumsum(mask.astype(jnp.int32)) - 1
        ok = mask & (idx < cap)
        overflow = overflow + jnp.sum(mask & ~ok)
        target = jnp.where(ok, d * cap + idx, target)
    received: Dict[str, jnp.ndarray] = {}
    for name, arr in payload.items():
        buf = jnp.zeros((total + 1,) + arr.shape[1:], arr.dtype)
        buf = buf.at[target].set(arr)
        bucketed = buf[:total].reshape((n_shards, cap) + arr.shape[1:])
        swapped = jax.lax.all_to_all(
            bucketed, SHARD_AXIS, split_axis=0, concat_axis=0
        )
        received[name] = swapped.reshape((total,) + arr.shape[1:])
    return received, overflow
