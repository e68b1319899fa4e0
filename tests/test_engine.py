"""Engine registry + single-device engine equivalences (the multi-device
sharded checks run in subprocesses — see test_engine_sharded.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_engine_matches_oracle
from repro.core import ProtocolConfig, run_engine, run_oracle, run_wavefront
from repro.engine import (
    ENGINES,
    Engine,
    SequentialEngine,
    ShardedEngine,
    WavefrontEngine,
    get_engine,
    make_engine,
)
from repro.mabs.voter import VoterModel
from repro.topology import ring, watts_strogatz


def test_registry_contents():
    assert {"sequential", "wavefront", "wavefront_overlap", "sharded",
            "sharded_replicated", "sharded_overlap"} <= set(ENGINES)
    assert get_engine("wavefront") is WavefrontEngine
    assert get_engine("sequential") is SequentialEngine
    assert get_engine("sharded") is ShardedEngine
    assert get_engine("wavefront_overlap").default_overlap
    assert get_engine("sharded_overlap").default_overlap
    with pytest.raises(ValueError, match="unknown engine"):
        get_engine("gpu-magic")


def test_make_engine_and_interface():
    m = VoterModel(ring(32, 4))
    eng = make_engine("wavefront", m, window=16)
    assert isinstance(eng, Engine)
    assert eng.window == 16
    st = m.init_state(jax.random.key(0))
    out, stats = eng.run(st, 40, seed=0)
    assert stats["total_tasks"] == 40 and stats["n_windows"] == 3
    assert out["opinions"].shape == st["opinions"].shape


# a single partial window, one full window, full windows, partial tails
@pytest.mark.parametrize("total", [1, 31, 32, 64, 100, 133])
def test_wavefront_engine_bitexact(total):
    m = VoterModel(watts_strogatz(64, 4, 0.2, jax.random.key(5)))
    st0 = m.init_state(jax.random.key(1))
    stats = assert_engine_matches_oracle(m, st0, total, engine="wavefront",
                                         window=32, seed=2)
    assert stats["total_waves"] >= 1
    assert stats["n_windows"] == -(-total // 32)


@pytest.mark.parametrize("seed", [-3, 2**31 + 5, 2**40 + 7])
def test_wavefront_engine_bitexact_any_seed(seed):
    """The device loop makes the run's key itself: seeds past 32 bits,
    and negative ones, give the oracle's task stream too."""
    m = VoterModel(watts_strogatz(64, 4, 0.2, jax.random.key(5)))
    st0 = m.init_state(jax.random.key(1))
    assert_engine_matches_oracle(m, st0, 70, engine="wavefront", window=32,
                                 seed=seed)


def test_wavefront_device_loop_compiles_once(monkeypatch):
    """The barrier run is one device program whose run length is traced:
    runs of three lengths trace the window loop once and share one
    executable."""
    m = VoterModel(ring(48, 4))
    st0 = m.init_state(jax.random.key(0))
    eng = make_engine("wavefront", m, window=16)
    traces = []
    schedule = eng._schedule_window
    monkeypatch.setattr(eng, "_schedule_window",
                        lambda *a: traces.append(1) or schedule(*a))
    for total in (5, 40, 70):
        _, stats = eng.run(st0, total, seed=1)
        assert stats["total_tasks"] == total
    assert len(traces) == 1
    assert eng._run_windows._cache_size() == 1


def test_wavefront_engine_does_not_clobber_caller_state():
    """No donation: the caller's state is unchanged and still readable
    after a run (callers replay from it, as the oracle comparison
    does)."""
    m = VoterModel(ring(48, 4))
    st0 = m.init_state(jax.random.key(3))
    before = np.asarray(st0["opinions"]).copy()
    out, _ = make_engine("wavefront", m, window=16).run(st0, 64, seed=0)
    assert not st0["opinions"].is_deleted()
    assert (np.asarray(st0["opinions"]) == before).all()
    assert not (np.asarray(out["opinions"]) == before).all()


def test_run_engine_routes_by_config_and_kwarg():
    m = VoterModel(ring(32, 4))
    st0 = m.init_state(jax.random.key(0))
    cfg = ProtocolConfig(window=16, engine="sequential")
    seq, stats = run_engine(m, st0, 20, seed=0, config=cfg)
    assert stats["mean_parallelism"] == 1.0
    wf, wstats = run_engine(m, st0, 20, seed=0, config=cfg,
                            engine="wavefront")
    assert bool(jnp.all(seq["opinions"] == wf["opinions"]))
    assert wstats["mean_parallelism"] >= 1.0


def test_sharded_engine_exact_on_default_mesh():
    """The sharded engine is exact on whatever mesh the process sees —
    1 device in the tier-1 run, 8 in the multi-device CI job (its full
    multi-device sweep runs in the subprocess tests)."""
    m = VoterModel(ring(48, 4))
    st0 = m.init_state(jax.random.key(3))
    stats = assert_engine_matches_oracle(m, st0, 70, engine="sharded",
                                         window=32, seed=1)
    assert stats["n_devices"] == jax.device_count()


def test_sharded_engine_does_not_clobber_caller_state():
    """Donation must only ever touch the engine's own device_put copy."""
    m = VoterModel(ring(48, 4))
    st0 = m.init_state(jax.random.key(3))
    before = np.asarray(st0["opinions"]).copy()
    run_engine(m, st0, 64, seed=0,
               config=ProtocolConfig(window=32), engine="sharded")
    assert (np.asarray(st0["opinions"]) == before).all()
