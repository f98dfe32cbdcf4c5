"""Parallel-layer tests on the 8-device virtual CPU mesh: mesh specs,
logical sharding rules, FSDP auto-sharding, collectives under shard_map.
"""

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401  (forces CPU platform before jax use)
from jax import shard_map
from pytorch_operator_tpu.parallel import (
    collectives,
    fsdp_spec,
    fsdp_shardings,
    logical_to_spec,
    make_mesh,
    parse_mesh_spec,
    resolve_axis_sizes,
)


class TestMeshSpec:
    def test_parse_string(self):
        assert parse_mesh_spec("dp=2,tp=4") == {"dp": 2, "tp": 4}

    def test_wildcard_resolution(self):
        assert resolve_axis_sizes("fsdp=-1,tp=2", 8) == {"fsdp": 4, "tp": 2}

    def test_canonical_order(self):
        axes = resolve_axis_sizes({"tp": 2, "dp": 4}, 8)
        assert list(axes.keys()) == ["dp", "tp"]  # tp innermost

    def test_product_mismatch_rejected(self):
        with pytest.raises(ValueError, match="!= device count"):
            resolve_axis_sizes("dp=3", 8)

    def test_bad_axis_rejected(self):
        with pytest.raises(ValueError, match="unknown mesh axis"):
            parse_mesh_spec("zz=2")

    def test_two_wildcards_rejected(self):
        with pytest.raises(ValueError, match="one -1 wildcard"):
            parse_mesh_spec("dp=-1,tp=-1")

    def test_make_mesh(self):
        mesh = make_mesh("dp=2,fsdp=2,tp=2")
        assert mesh.axis_names == ("dp", "fsdp", "tp")
        assert mesh.devices.shape == (2, 2, 2)


class TestHybridMesh:
    """Multi-slice meshes: dcn axes outermost, ici axes within a slice."""

    def test_axes_and_shape(self):
        from pytorch_operator_tpu.parallel import make_hybrid_mesh

        mesh = make_hybrid_mesh(ici="fsdp=-1,tp=2", dcn="dp=2")
        assert mesh.axis_names == ("dp", "fsdp", "tp")
        assert mesh.devices.shape == (2, 2, 2)
        # dcn outermost: each dp row holds one contiguous 4-device "slice".
        flat = mesh.devices.reshape(2, -1)
        ids = [[d.id for d in row] for row in flat]
        assert ids[0] == sorted(ids[0]) and ids[1] == sorted(ids[1])
        assert max(ids[0]) < min(ids[1])

    def test_gradient_psum_over_dcn_axis(self):
        """The intended layout: fsdp/tp traffic inside a slice, one dp
        gradient reduction across DCN — exercised with a real psum."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pytorch_operator_tpu.parallel import make_hybrid_mesh

        mesh = make_hybrid_mesh(ici="fsdp=4", dcn="dp=2")
        x = jnp.arange(8.0).reshape(8, 1)
        xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"))))
        total = jax.jit(lambda a: a.sum())(xs)
        assert float(total) == sum(range(8))

    def test_overlapping_axes_rejected(self):
        from pytorch_operator_tpu.parallel import make_hybrid_mesh

        with pytest.raises(ValueError, match="both"):
            make_hybrid_mesh(ici="dp=4", dcn="dp=2")

    def test_dcn_wildcard_rejected(self):
        from pytorch_operator_tpu.parallel import make_hybrid_mesh

        with pytest.raises(ValueError, match="explicit"):
            make_hybrid_mesh(ici="fsdp=4", dcn="dp=-1")

    def test_empty_dcn_degrades_to_plain_mesh(self):
        from pytorch_operator_tpu.parallel import make_hybrid_mesh

        mesh = make_hybrid_mesh(ici="dp=-1", dcn="")
        assert mesh.devices.shape == (8,)

    def test_at_dcn_suffix_in_make_mesh(self):
        """The --mesh / TPUJOB_MESH user syntax for hybrid layouts."""
        mesh = make_mesh("dp=2@dcn,fsdp=-1,tp=2")
        assert mesh.axis_names == ("dp", "fsdp", "tp")
        assert mesh.devices.shape == (2, 2, 2)

    def test_all_dcn_spec(self):
        """Pure cross-slice data parallel: one device per slice, no
        phantom ici axes."""
        mesh = make_mesh("dp=8@dcn")
        assert mesh.axis_names == ("dp",)
        assert mesh.devices.shape == (8,)

    def test_all_dcn_spec_with_leftover_devices_rejected(self):
        from pytorch_operator_tpu.parallel import make_hybrid_mesh

        with pytest.raises(ValueError, match="1 device per slice"):
            make_hybrid_mesh(ici="", dcn="dp=2")

    def test_parse_mesh_spec_accepts_dcn_suffix(self):
        """The canonical parser must not choke on the documented syntax."""
        assert parse_mesh_spec("dp=2@dcn,tp=2") == {"dp": 2, "tp": 2}
        from pytorch_operator_tpu.parallel.mesh import split_hybrid_spec

        assert split_hybrid_spec("dp=2@dcn,fsdp=-1,tp=2") == ("fsdp=-1,tp=2", "dp=2")


class TestShardingRules:
    def test_logical_to_spec(self):
        mesh = make_mesh("dp=2,tp=4")
        spec = logical_to_spec(("batch", "seq", "heads"), mesh=mesh)
        assert tuple(spec) == ("dp", None, "tp")

    def test_missing_mesh_axis_replicates(self):
        mesh = make_mesh("dp=8")
        spec = logical_to_spec(("batch", "mlp"), mesh=mesh)  # no tp axis
        assert tuple(spec) == ("dp",)

    def test_fsdp_spec_picks_divisible_dim(self):
        mesh = make_mesh("fsdp=4,tp=2")
        spec = fsdp_spec((333, 1024), mesh)
        assert tuple(spec) == (None, "fsdp")

    def test_fsdp_small_param_replicates(self):
        mesh = make_mesh("fsdp=8")
        assert tuple(fsdp_spec((128,), mesh)) == ()

    def test_fsdp_shardings_tree(self):
        import jax.numpy as jnp

        mesh = make_mesh("fsdp=8")
        params = {"w": jnp.zeros((512, 512)), "b": jnp.zeros((8,))}
        sh = fsdp_shardings(params, mesh, min_elements=1024)
        assert tuple(sh["w"].spec) == ("fsdp",)
        assert tuple(sh["b"].spec) == ()


class TestCollectives:
    def test_psum_ring_reduce_scatter(self):
        import jax
        import jax.numpy as jnp
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = make_mesh("dp=8")
        x = jax.device_put(
            jnp.arange(8.0), NamedSharding(mesh, PartitionSpec("dp"))
        )

        @jax.jit
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=PartitionSpec("dp"),
            out_specs=(PartitionSpec(), PartitionSpec("dp"), PartitionSpec("dp")),
        )
        def f(xs):
            total = collectives.psum(jnp.sum(xs), "dp")
            ring = collectives.ring_shift(xs, "dp", shift=1)
            gathered = collectives.all_gather(xs, "dp")
            rs = collectives.reduce_scatter(gathered, "dp")
            return total, ring, rs

        total, ring, rs = f(x)
        assert float(total) == 28.0
        np.testing.assert_array_equal(np.asarray(ring), np.roll(np.arange(8.0), 1))
        # reduce_scatter(all_gather(x)) == x * n? No: psum_scatter of the
        # full gathered vector sums 8 copies then scatters -> x * 8... each
        # shard holds the same gathered vector, so scatter_i = 8 * x_i.
        np.testing.assert_array_equal(np.asarray(rs), np.arange(8.0) * 8)

    def test_axis_index(self):
        import jax
        import jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec

        mesh = make_mesh("dp=8")

        @jax.jit
        @partial(
            shard_map, mesh=mesh, in_specs=(), out_specs=PartitionSpec("dp")
        )
        def f():
            return jnp.reshape(collectives.axis_index("dp"), (1,))

        np.testing.assert_array_equal(np.asarray(f()), np.arange(8))
