"""Wave-level kernel sweeps (Pallas interpret vs the scan reference) and
the finite-worker list-scheduling invariants of ``wave_levels_capped``."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.records import wave_levels, wave_levels_capped
from repro.kernels.levels.levels import wave_levels_pallas
from repro.kernels.levels.ops import wave_levels as wave_levels_op
from repro.kernels.levels.ref import wave_levels_ref


def _random_window(seed, *, lower=True):
    rng = np.random.RandomState(seed)
    w = rng.randint(3, 300)
    density = rng.rand() * 0.6
    conf = rng.rand(w, w) < density
    if lower:
        conf = np.tril(conf, k=-1)
    valid = rng.rand(w) < (1.0 if seed % 3 else 0.8)
    return conf, valid


# ------------------------------------------------------------ pallas kernel
@pytest.mark.parametrize("seed", range(25))
def test_levels_pallas_matches_scan(seed):
    """Blocked kernel == scan reference on random (padded, partly invalid)
    windows, across block boundaries (w up to 300 with 128-blocks)."""
    conf, valid = _random_window(seed)
    ref = wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid))
    out = wave_levels_pallas(jnp.asarray(conf), jnp.asarray(valid),
                             interpret=True)
    assert bool(jnp.all(out == ref))


@pytest.mark.parametrize("seed", range(8))
def test_levels_pallas_arbitrary_matrices(seed):
    """Same convention as the scan for non-lower-triangular inputs:
    at/above-diagonal entries and invalid targets contribute nothing."""
    conf, valid = _random_window(seed, lower=False)
    ref = wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid))
    out = wave_levels_pallas(jnp.asarray(conf), jnp.asarray(valid),
                             interpret=True)
    assert bool(jnp.all(out == ref))


@pytest.mark.parametrize("w", [1, 2, 128, 129, 256])
def test_levels_pallas_shapes(w):
    rng = np.random.RandomState(w)
    conf = np.tril(rng.rand(w, w) < 0.3, k=-1)
    valid = np.ones(w, bool)
    ref = wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid))
    out = wave_levels_pallas(jnp.asarray(conf), jnp.asarray(valid),
                             interpret=True)
    assert bool(jnp.all(out == ref))


# ------------------------------------------------- carry-over base floor
def _brute_levels_with_base(conf, valid, base):
    """O(W²) host-side oracle for the floored recurrence."""
    w = conf.shape[0]
    lv = np.full(w, -1, np.int64)
    for i in range(w):
        if not valid[i]:
            continue
        deps = [lv[j] for j in range(i) if conf[i, j]]
        lv[i] = max(int(base[i]), (max(deps) + 1) if deps else 0)
    return lv


@pytest.mark.parametrize("seed", range(12))
def test_levels_base_floor_matches_brute_force(seed):
    """The overlapped engines' carry frontier enters as a per-task level
    floor; scan ref and blocked Pallas kernel must both honor it."""
    conf, valid = _random_window(seed)
    rng = np.random.RandomState(seed + 1000)
    base = rng.randint(0, 9, size=conf.shape[0])
    brute = _brute_levels_with_base(conf, valid, base)
    ref = wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid),
                          jnp.asarray(base, jnp.int32))
    out = wave_levels_pallas(jnp.asarray(conf), jnp.asarray(valid),
                             jnp.asarray(base, jnp.int32), interpret=True)
    assert (np.asarray(ref) == brute).all()
    assert bool(jnp.all(out == ref))


def test_levels_base_zero_is_classic_recurrence():
    conf, valid = _random_window(5)
    zero = jnp.zeros((conf.shape[0],), jnp.int32)
    assert bool(jnp.all(
        wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid))
        == wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid), zero)))
    assert bool(jnp.all(
        wave_levels(jnp.asarray(conf), jnp.asarray(valid))
        == wave_levels(jnp.asarray(conf), jnp.asarray(valid), base=zero)))


def test_levels_op_backends_and_default():
    conf, valid = _random_window(11)
    ref = wave_levels_ref(jnp.asarray(conf), jnp.asarray(valid))
    for backend in ("jnp", "pallas"):
        out = wave_levels_op(conf, valid, backend=backend,
                             interpret=True)
        assert bool(jnp.all(out == ref))
    # core.records.wave_levels is the default route execute_window uses
    assert bool(jnp.all(wave_levels(jnp.asarray(conf),
                                    jnp.asarray(valid)) == ref))
    with pytest.raises(ValueError):
        wave_levels_op(conf, valid, backend="cuda")


# ------------------------------------------------------ wave_levels_capped
@pytest.mark.parametrize("seed", range(20))
def test_capped_matches_uncapped_at_infinite_workers(seed):
    """n_workers >= W removes every capacity constraint: the capped
    schedule degenerates to the pure dependence levels."""
    conf, valid = _random_window(seed)
    w = conf.shape[0]
    lv = np.asarray(wave_levels(jnp.asarray(conf), jnp.asarray(valid)))
    capped = wave_levels_capped(conf, valid, n_workers=w)
    assert (capped == lv).all()


@pytest.mark.parametrize("seed", range(20))
@pytest.mark.parametrize("n_workers", [1, 2, 5])
def test_capped_capacity_invariant(seed, n_workers):
    """No wave may hold more than n_workers tasks."""
    conf, valid = _random_window(seed)
    capped = wave_levels_capped(conf, valid, n_workers=n_workers)
    lv = capped[capped >= 0]
    if lv.size:
        assert np.bincount(lv).max() <= n_workers
    assert (capped[~np.asarray(valid)] == -1).all()


@pytest.mark.parametrize("seed", range(20))
@pytest.mark.parametrize("n_workers", [1, 3])
def test_capped_lower_bounded_by_dependence_levels(seed, n_workers):
    """Capacity can only push tasks later: capped >= uncapped level, and
    dependencies still strictly order the waves."""
    conf, valid = _random_window(seed)
    lv = np.asarray(wave_levels(jnp.asarray(conf), jnp.asarray(valid)))
    capped = wave_levels_capped(conf, valid, n_workers=n_workers)
    v = np.asarray(valid)
    assert (capped[v] >= lv[v]).all()
    ii, jj = np.nonzero(np.asarray(conf) & v[:, None] & v[None, :]
                        & np.tril(np.ones_like(conf, dtype=bool), k=-1))
    assert (capped[ii] > capped[jj]).all()
