"""Disease-spreading MABS (paper §4.2): SIRS dynamics on a ring graph under
the protocol, with epidemic curves and granularity (s) exploration.

Usage:  PYTHONPATH=src python examples/epidemic.py
"""
import jax
import numpy as np

from repro.core import ProtocolConfig, run_wavefront, simulate_protocol
from repro.core.wavefront import window_schedule_stats
from repro.mabs.sir import SIRConfig, SIRModel
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = SIRConfig(n_agents=2_000, k=14, subset_size=50,
                    p_si=0.8, p_ir=0.1, p_rs=0.3, i0=0.02)
    model = SIRModel(cfg)
    state = model.init_state(jax.random.key(0))

    print("== epidemic trajectory under the wavefront engine ==")
    pcfg = ProtocolConfig(window=2 * cfg.n_subsets, strict=True)
    for step in range(10):
        state, _ = run_wavefront(model, state, cfg.tasks_per_step(),
                                 seed=step, config=pcfg)
        s = np.asarray(state["states"])
        frac = np.bincount(s, minlength=3) / cfg.n_agents
        bar = "#" * int(frac[1] * 60)
        print(f"  step {step:2d}  S={frac[0]:.2f} I={frac[1]:.2f} "
              f"R={frac[2]:.2f}  {bar}")

    print("== schedule structure at different granularities ==")
    for s_sz in (10, 50, 200):
        m = SIRModel(SIRConfig(n_agents=2_000, k=14, subset_size=s_sz))
        rec = m.create_tasks(jax.random.key(0), 0, 2 * m.cfg.n_subsets)
        import jax.numpy as jnp

        stats = window_schedule_stats(
            m, rec, jnp.ones(2 * m.cfg.n_subsets, bool))
        print(f"  s={s_sz:4d}: {stats['n_tasks']} tasks -> "
              f"{stats['n_waves']} waves "
              f"(parallelism {stats['mean_parallelism']:.1f}, "
              f"conflict density {stats['conflict_density']:.3f})")

    print("== worker scaling (protocol DES, paper Fig. 3 slice) ==")
    m = SIRModel(SIRConfig(n_agents=2_000, k=14, subset_size=100))
    tasks = m.cfg.tasks_per_step() * 5
    for n in (1, 2, 4, 5):
        r = simulate_protocol(m.des_model(), tasks,
                              config=ProtocolConfig(n_workers=n))
        print(f"  n={n}: T={r.makespan*1e3:.2f} ms")


if __name__ == "__main__":
    main()
