"""Reproduction of paper Fig. 2 — cultural dynamics T(s=F; n), C=6.

Methodology (DESIGN.md §10): the calibrated discrete-event simulator
replays the exact worker-chain protocol; per-task model-execution cost is
*measured* on this machine from the jitted vectorized Axelrod executor
(cost(F) fit as a + b·F), and protocol overheads use the DESCosts
constants. Paper scale is 2e6 steps / N=1e4; default here is scaled down
(--tasks) since T is linear in task count in steady state — the claims
are about the SHAPE of T(s; n).

Output CSV: name,F,n_workers,T_mean,T_sem  (5 seeds, as in the paper).
"""
from __future__ import annotations

import sys

import jax
import numpy as np

from repro.core import DESCosts, ProtocolConfig, simulate_protocol
from repro.engine import make_engine
from repro.mabs.axelrod import AxelrodConfig, AxelrodModel
from repro.utils.timing import median_time


def calibrate_task_cost(n_agents=10_000, features=(3, 50, 150, 300, 500)):
    """Measure per-task execution cost of the vectorized executor and fit
    cost(F) = a + b·F."""
    xs, ys = [], []
    for f in features:
        m = AxelrodModel(AxelrodConfig(n_agents=n_agents, n_features=f))
        st = m.init_state(jax.random.key(0))
        eng = make_engine("wavefront", m, window=256)
        # one window of 256 tasks: schedule + waves, fenced on the state
        t = median_time(lambda: eng.run(st, 256, seed=1)[0],
                        repeats=3, warmup=1)
        xs.append(f)
        ys.append(t / 256.0)      # per-task cost of the vectorized engine
    A = np.vstack([np.ones(len(xs)), xs]).T
    (a, b), *_ = np.linalg.lstsq(A, np.asarray(ys), rcond=None)
    return max(a, 1e-8), max(b, 1e-10)


def run(n_tasks=30_000, seeds=(0, 1, 2, 3, 4), features=(3, 50, 150, 300, 500),
        workers=(1, 2, 3, 4, 5), quick=False):
    if quick:
        n_tasks, seeds = 5_000, (0, 1)
    a, b = calibrate_task_cost()
    rows = []
    for f in features:
        for n in workers:
            ts = []
            for seed in seeds:
                m = AxelrodModel(AxelrodConfig(n_agents=10_000,
                                               n_features=f))
                des = m.des_model(seed=seed,
                                  exec_cost=lambda r, f=f: a + b * f)
                r = simulate_protocol(
                    des, n_tasks,
                    config=ProtocolConfig(n_workers=n, tasks_per_cycle=6))
                ts.append(r.makespan)
            mean = float(np.mean(ts))
            sem = float(np.std(ts) / np.sqrt(len(ts)))
            rows.append(("fig2_axelrod", f, n, mean, sem))
            print(f"fig2_axelrod,F={f},n={n},{mean*1e3:.2f}ms,{sem*1e3:.3f}")
    return rows


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
