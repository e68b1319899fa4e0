"""Unit tests for the per-wave halo-split builders
(``distributed.sharding.wave_halo_split`` / ``wave_halo_gather`` /
``wave_slab_counts``) — pure-jnp layout checks plus the zero-width /
empty-wave no-op contract. These run in-process on the default single
device (``halo_gather`` degenerates to a self-psum on a 1-device mesh);
the multi-device behavior is covered end to end by the engine and
differential suites."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import (
    AGENT_AXIS,
    halo_gather,
    wave_halo_gather,
    wave_halo_split,
    wave_slab_counts,
)


def _slab_rows(slabs, chunk_start, w):
    """Host-side: the valid rows of wave w's chunk range."""
    c0, c1 = int(chunk_start[w]), int(chunk_start[w + 1])
    rows = np.asarray(slabs)[c0:c1].reshape(-1)
    return rows[rows >= 0]


def test_split_layout_partitions_rows_by_wave():
    """Every valid row lands in exactly its task's wave slab, waves own
    disjoint chunk ranges, and padding is -1."""
    rows = jnp.asarray([[3, 7], [1, -1], [5, 6], [2, 7], [-1, -1]],
                       dtype=jnp.int32)
    levels = jnp.asarray([0, 1, 0, 2, 1], dtype=jnp.int32)
    slabs, chunk_start = wave_halo_split(rows, levels, n_waves_max=5,
                                         chunk=3)
    counts = wave_slab_counts(rows, levels, n_waves_max=5)
    assert counts.tolist() == [4, 1, 2, 0, 0]
    # chunk ranges: ceil(4/3)=2, ceil(1/3)=1, ceil(2/3)=1, 0, 0
    assert chunk_start.tolist() == [0, 2, 3, 4, 4, 4]
    assert sorted(_slab_rows(slabs, chunk_start, 0)) == [3, 5, 6, 7]
    assert sorted(_slab_rows(slabs, chunk_start, 1)) == [1]
    assert sorted(_slab_rows(slabs, chunk_start, 2)) == [2, 7]
    # everything past the allocated chunks is padding
    assert bool(jnp.all(slabs[int(chunk_start[-1]):] == -1))


def test_split_drops_invalid_tasks_and_rows():
    """Level -1 (executed/invalid) tasks and -1 row slots contribute
    nothing; levels >= n_waves_max (an overlapped pair's beyond-horizon
    tasks) are dropped rather than scattered."""
    rows = jnp.asarray([[4, 4], [9, 2], [8, -1]], dtype=jnp.int32)
    levels = jnp.asarray([-1, 7, 1], dtype=jnp.int32)
    slabs, chunk_start = wave_halo_split(rows, levels, n_waves_max=2,
                                         chunk=2)
    counts = wave_slab_counts(rows, levels, n_waves_max=2)
    assert counts.tolist() == [0, 1]
    assert chunk_start.tolist() == [0, 0, 1]
    assert _slab_rows(slabs, chunk_start, 1).tolist() == [8]


def test_empty_wave_owns_no_chunks():
    """A fully-drained wave (level gap after rebasing in overlapped
    mode) owns a zero-width chunk range — the executor's chunk loop
    body never runs, so no collective is issued for it."""
    rows = jnp.asarray([[0, 1], [2, 3]], dtype=jnp.int32)
    levels = jnp.asarray([0, 2], dtype=jnp.int32)  # wave 1 is empty
    slabs, chunk_start = wave_halo_split(rows, levels, n_waves_max=4,
                                         chunk=8)
    assert chunk_start.tolist() == [0, 1, 1, 2, 2]
    assert int(chunk_start[2]) - int(chunk_start[1]) == 0  # wave 1: no-op


def test_counts_bound_by_total_valid_rows():
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randint(-1, 50, size=(32, 4)), dtype=jnp.int32)
    levels = jnp.asarray(rng.randint(-1, 10, size=(32,)), dtype=jnp.int32)
    counts = wave_slab_counts(rows, levels, n_waves_max=32)
    n_valid = int(jnp.sum((rows >= 0) & (levels[:, None] >= 0)))
    assert int(jnp.sum(counts)) == n_valid


def test_zero_width_gather_is_a_clean_noop():
    """``halo_gather`` on a zero-width halo (and ``wave_halo_gather`` on
    zero-width chunks) must return an empty result without materializing
    a degenerate collective."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), (AGENT_AXIS,))
    local = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    empty_halo = jnp.zeros((0,), jnp.int32)
    slabs0 = jnp.zeros((3, 0), jnp.int32)   # chunked layout, width 0

    def f(loc):
        g = halo_gather(loc, empty_halo, shard_n=6)
        gc, slab = wave_halo_gather(loc, slabs0, jnp.int32(1), shard_n=6)
        return g, gc, slab

    g, gc, slab = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(AGENT_AXIS),),
        out_specs=(P(), P(), P()), check_vma=False))(local)
    assert g.shape == (0, 2) and gc.shape == (0, 2) and slab.shape == (0,)


def test_gather_matches_monolithic_on_one_device():
    """Gathering a wave's chunks one by one delivers exactly the same
    rows as a monolithic gather of that wave's slab."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), (AGENT_AXIS,))
    state = jnp.arange(20, dtype=jnp.float32)
    rows = jnp.asarray([[3, 17], [5, -1], [11, 3]], dtype=jnp.int32)
    levels = jnp.asarray([0, 1, 0], dtype=jnp.int32)
    slabs, chunk_start = wave_halo_split(rows, levels, n_waves_max=3,
                                         chunk=2)

    def f(loc):
        out = jnp.zeros((20,), jnp.float32)
        c0, c1 = chunk_start[0], chunk_start[1]

        def body(carry):
            c, acc = carry
            g, slab = wave_halo_gather(loc, slabs, c, shard_n=20)
            acc = acc.at[jnp.where(slab >= 0, slab, 20)].set(
                g, mode="drop")
            return c + 1, acc

        _, out = jax.lax.while_loop(lambda c: c[0] < c1, body, (c0, out))
        return out

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(AGENT_AXIS),),
                            out_specs=P(), check_vma=False))(state)
    expect = np.zeros(20, np.float32)
    for r in (3, 17, 11):   # wave 0's rows
        expect[r] = float(state[r])
    np.testing.assert_array_equal(np.asarray(out), expect)
