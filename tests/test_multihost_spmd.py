"""Multi-process jax.distributed world launched through the runtime.

Two ranks x 4 virtual CPU devices = one 8-device global mesh; the psum
crosses process boundaries over Gloo — the CPU stand-in for XLA
collectives over ICI/DCN on a TPU pod.
"""
import numpy as np
import pytest

import ray_tpu
from ray_tpu.train.multihost import MultiHostSpmd

ENV = {"JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def _psum_fn(rank, world):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    shard_map = getattr(jax, "shard_map", None)
    if shard_map is None:                     # jax < 0.5 keeps it in
        from jax.experimental.shard_map import shard_map  # noqa: PLC0415
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("dp",))
    n = jax.device_count()
    x = jax.make_array_from_callback(
        (n,), NamedSharding(mesh, P("dp")),
        lambda idx: np.ones((1,)) * (rank + 1))   # one element per device
    out = jax.jit(shard_map(lambda v: jax.lax.psum(v, "dp"),
                            mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp")))(x)
    return float(np.asarray(out.addressable_shards[0].data)[0])


@pytest.mark.slow
def test_two_rank_world_psum(rt):
    group = MultiHostSpmd(2, resources_per_host={"CPU": 1},
                          env_per_host=ENV)
    try:
        assert group.world_devices == 8
        results = group.run(_psum_fn)
        # ranks contribute 4x1 + 4x2 = 12 across process boundaries
        assert results == [12.0, 12.0]
    finally:
        group.shutdown()


def _shard_sum(rank, world, shard):
    return float(shard.sum())


def test_run_sharded_per_rank_batches(rt):
    """run_sharded ships a DIFFERENT payload to each rank as an object
    ref — multihost data loading over the transfer plane: each rank's
    worker resolves only its own shard (driver brokers locations; on a
    multi-node cluster the bytes move holder -> rank directly)."""
    group = MultiHostSpmd(2, resources_per_host={"CPU": 1},
                          env_per_host=ENV)
    try:
        shards = [np.full((20_000,), float(r + 1)) for r in range(2)]
        out = group.run_sharded(_shard_sum, shards)
        assert out == [20_000.0, 40_000.0]
        with pytest.raises(ValueError, match="one shard per rank"):
            group.run_sharded(_shard_sum, shards[:1])
    finally:
        group.shutdown()
