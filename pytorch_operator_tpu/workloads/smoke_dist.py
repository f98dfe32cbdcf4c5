"""Distributed smoke test — the rendezvous/collectives canary.

Reference: ``examples/smoke-dist/dist_sendrecv.py`` — a minimal
``dist.send/recv`` ring proving the operator's env wiring end-to-end
(SURVEY.md §4 "Distributed smoke test"). TPU-native version: join the
jax.distributed world from the supervisor-injected env, then

1. allgather every process id (rendezvous + addressing proof),
2. global psum over a device-sharded array (cross-process collective),
3. a ppermute ring shift under shard_map (the send/recv ring itself).

Exit 0 only if every check passes on every process.
"""

from __future__ import annotations

import sys

from ..runtime import rendezvous


def main() -> int:
    world = rendezvous.initialize_from_env()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel import collectives, make_mesh

    n_dev = jax.device_count()
    print(
        f"[smoke-dist] rank {world.process_id}/{world.num_processes}: "
        f"{jax.process_count()} processes, {n_dev} global devices",
        flush=True,
    )

    # 1. rendezvous proof: every process id is visible everywhere.
    if world.num_processes > 1:
        from jax.experimental import multihost_utils

        ranks = multihost_utils.process_allgather(
            jnp.array([world.process_id], dtype=jnp.int32)
        )
        got = sorted(ranks.ravel().tolist())
        want = list(range(world.num_processes))
        if got != want:
            print(f"[smoke-dist] FAIL allgather: got {got}, want {want}", flush=True)
            return 1

    # 2+3. collectives over a dp mesh spanning all global devices.
    mesh = make_mesh({"dp": n_dev})
    x = jnp.arange(float(n_dev))
    x = jax.device_put(x, NamedSharding(mesh, PartitionSpec("dp")))

    from functools import partial

    from jax import shard_map

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=PartitionSpec("dp"),
        out_specs=(PartitionSpec(), PartitionSpec("dp")),
    )
    def ring_check(xs):
        total = collectives.psum(jnp.sum(xs), "dp")
        shifted = collectives.ring_shift(xs, "dp", shift=1)
        return total, shifted

    total, shifted = ring_check(x)
    want_total = float(n_dev * (n_dev - 1) // 2)
    ok_total = float(total) == want_total
    # ring shift moves shard i to position (i+1) mod n — a cyclic roll.
    # Replicate before device_get: per-process shards of a distributed array
    # are not all addressable locally.
    replicate = jax.jit(
        lambda y: y, out_shardings=NamedSharding(mesh, PartitionSpec())
    )
    want_shifted = jnp.roll(jnp.arange(float(n_dev)), 1)
    ok_ring = bool(
        jnp.array_equal(jax.device_get(replicate(shifted)), want_shifted)
    )
    if not ok_total or not ok_ring:
        print(
            f"[smoke-dist] FAIL collectives: psum={total} (want {want_total}), "
            f"ring ok={ok_ring}",
            flush=True,
        )
        return 1

    rendezvous.report_first_step()
    dev = rendezvous.report_device()
    print(
        f"[smoke-dist] rank {world.process_id}: OK on {dev['platform']} "
        f"device(s) {dev['local_device_ids']} of {dev['device_count']}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
