"""Collective layer tests (reference test model:
``python/ray/util/collective/tests/``)."""
import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import collective as col


@ray_tpu.remote
class Member:
    def __init__(self, world_size: int, rank: int, group_name: str):
        self.rank = rank
        self.world = world_size
        col.init_collective_group(
            world_size, rank, backend="host", group_name=group_name
        )
        self.group = group_name

    def do_allreduce(self):
        x = np.full((4,), float(self.rank + 1), np.float32)
        return col.allreduce(x, self.group)

    def do_broadcast(self):
        x = (
            np.arange(3, dtype=np.float32)
            if self.rank == 0
            else np.zeros(3, np.float32)
        )
        return col.broadcast(x, src_rank=0, group_name=self.group)

    def do_allgather(self):
        return col.allgather(np.array([self.rank], np.int64), self.group)

    def do_reducescatter(self):
        x = np.arange(self.world * 2, dtype=np.float32)
        return col.reducescatter(x, self.group)

    def do_barrier(self):
        col.barrier(self.group)
        return self.rank

    def do_sendrecv(self):
        if self.rank == 0:
            col.send(np.array([42.0]), dst_rank=1, group_name=self.group)
            return None
        return col.recv(src_rank=0, group_name=self.group)

    def rank_info(self):
        return col.get_rank(self.group), col.get_collective_group_size(self.group)


@pytest.fixture
def members(rt_start):
    world = 3
    ms = [Member.remote(world, r, "g1") for r in range(world)]
    yield ms
    for m in ms:
        ray_tpu.kill(m)


def test_allreduce(members):
    outs = ray_tpu.get([m.do_allreduce.remote() for m in members])
    for o in outs:
        np.testing.assert_allclose(o, np.full((4,), 6.0))


def test_broadcast(members):
    outs = ray_tpu.get([m.do_broadcast.remote() for m in members])
    for o in outs:
        np.testing.assert_allclose(o, np.arange(3, dtype=np.float32))


def test_allgather_and_rank(members):
    outs = ray_tpu.get([m.do_allgather.remote() for m in members])
    for o in outs:
        assert [int(v[0]) for v in o] == [0, 1, 2]
    infos = ray_tpu.get([m.rank_info.remote() for m in members])
    assert infos == [(0, 3), (1, 3), (2, 3)]


def test_reducescatter(members):
    outs = ray_tpu.get([m.do_reducescatter.remote() for m in members])
    full = np.arange(6, dtype=np.float32) * 3
    got = np.concatenate(outs)
    np.testing.assert_allclose(got, full)


def test_barrier_and_sendrecv(members):
    assert sorted(ray_tpu.get([m.do_barrier.remote() for m in members])) == [0, 1, 2]
    outs = ray_tpu.get([m.do_sendrecv.remote() for m in members[:2]])
    assert outs[0] is None
    np.testing.assert_allclose(outs[1], [42.0])


@ray_tpu.remote
class PassiveMember:
    """No init_collective_group call — membership comes from the driver's
    declarative create_collective_group."""

    def do_allreduce(self, value: float):
        return col.allreduce(np.full((2,), value, np.float32), "gdecl")


def test_declarative_create_collective_group(rt_start):
    ms = [PassiveMember.remote() for _ in range(2)]
    col.create_collective_group(ms, 2, [0, 1], backend="host",
                                group_name="gdecl")
    outs = ray_tpu.get([m.do_allreduce.remote(float(i + 1))
                        for i, m in enumerate(ms)])
    for o in outs:
        np.testing.assert_allclose(o, np.full((2,), 3.0))
    col.destroy_collective_group("gdecl")
    for m in ms:
        ray_tpu.kill(m)


def test_world_size_mismatch_detected(rt_start):
    ms = [Member.remote(2, r, "gsize") for r in range(2)]
    ray_tpu.get([m.rank_info.remote() for m in ms])
    # Same group name, different world size, coordinator still alive → the
    # member's init fails loudly (raised from the actor's __init__)
    with pytest.raises(Exception, match="world_size"):
        bad = Member.remote(3, 0, "gsize")
        ray_tpu.get(bad.rank_info.remote())
    for m in ms:
        ray_tpu.kill(m)


def test_ici_product_allreduce_with_negatives():
    """PRODUCT must be exact for negative/zero inputs (no log/exp trick)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from ray_tpu.util.collective.types import ReduceOp

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    x = jnp.array([[-2.0], [3.0], [-1.0], [0.5]])

    f = shard_map(
        lambda xs: col.ici_allreduce(xs, "x", op=ReduceOp.PRODUCT),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
        check_vma=False,
    )
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.full((4, 1), 3.0))


def test_ici_collectives_in_jit():
    """In-jit collectives under shard_map on the 8-device CPU mesh."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    x = jnp.arange(8.0).reshape(4, 2)

    def body(xs):
        s = col.ici_allreduce(xs, "x")
        g = col.ici_allgather(xs, "x", axis=0)
        rs = col.ici_reducescatter(g, "x", axis=0)
        b = col.ici_broadcast(xs, "x", root=2)
        return s, g, rs, b

    f = shard_map(
        body, mesh=mesh, in_specs=P("x", None),
        out_specs=(P("x", None), P(None, None), P("x", None), P("x", None)),
        check_vma=False,
    )
    s, g, rs, b = jax.jit(f)(x)
    np.testing.assert_allclose(
        np.asarray(s), np.tile(x.sum(axis=0, keepdims=True), (4, 1))
    )
    np.testing.assert_allclose(np.asarray(g), np.asarray(x))
    np.testing.assert_allclose(np.asarray(rs), 4 * np.asarray(x))
    np.testing.assert_allclose(
        np.asarray(b), np.tile(np.asarray(x[2:3]), (4, 1))
    )
