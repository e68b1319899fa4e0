"""Reproduction of paper Fig. 3 — disease spreading T(s; n), C=6.

s = agents per subset (chain granularity). The paper's signature result:
T(s) spikes at small s (protocol overhead per tiny task), then stabilizes;
in the stable region T decreases with n, saturating around n=4; at small s
extra workers can *hurt*.

Costs: per-task execution cost measured from the vectorized SIR wave
executor (cost(s) = a + b·s), protocol overheads from DESCosts.

Output CSV: name,s,n_workers,T_mean,T_sem  (5 seeds).
"""
from __future__ import annotations

import sys

import jax
import numpy as np

from repro.core import DESCosts, ProtocolConfig, simulate_protocol
from repro.engine import make_engine
from repro.mabs.sir import SIRConfig, SIRModel
from repro.utils.timing import median_time


def calibrate_task_cost(n_agents=4_000, sizes=(10, 50, 200, 1000)):
    xs, ys = [], []
    for s in sizes:
        m = SIRModel(SIRConfig(n_agents=n_agents, k=14, subset_size=s))
        st = m.init_state(jax.random.key(0))
        w = min(64, 2 * m.cfg.n_subsets)
        eng = make_engine("wavefront", m, window=w)
        # one window of w tasks: schedule + waves, fenced on the state
        t = median_time(lambda: eng.run(st, w, seed=1)[0],
                        repeats=3, warmup=1)
        xs.append(s)
        ys.append(t / w)
    A = np.vstack([np.ones(len(xs)), xs]).T
    (a, b), *_ = np.linalg.lstsq(A, np.asarray(ys), rcond=None)
    return max(a, 1e-8), max(b, 1e-10)


def run(n_steps=40, seeds=(0, 1, 2, 3, 4),
        sizes=(10, 20, 40, 50, 100, 200, 500, 1000),
        workers=(1, 2, 3, 4, 5), quick=False):
    if quick:
        n_steps, seeds, sizes = 10, (0, 1), (10, 50, 200, 1000)
    a, b = calibrate_task_cost()
    rows = []
    for s in sizes:
        cfg = SIRConfig(n_agents=4_000, k=14, subset_size=s,
                        p_si=0.8, p_ir=0.1, p_rs=0.3)
        m = SIRModel(cfg)
        n_tasks = cfg.tasks_per_step() * n_steps
        for n in workers:
            ts = []
            for seed in seeds:
                des = m.des_model(exec_cost=lambda r, s=s: a + b * s)
                r = simulate_protocol(
                    des, n_tasks,
                    config=ProtocolConfig(n_workers=n, tasks_per_cycle=6))
                ts.append(r.makespan)
            mean = float(np.mean(ts))
            sem = float(np.std(ts) / np.sqrt(len(ts)))
            rows.append(("fig3_sir", s, n, mean, sem))
            print(f"fig3_sir,s={s},n={n},{mean*1e3:.2f}ms,{sem*1e3:.3f}")
    return rows


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
