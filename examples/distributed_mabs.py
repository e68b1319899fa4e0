import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Distributed MABS: the sharded execution engine — the paper's protocol
crossing the device boundary.

The ``sharded`` engine shards agent state into contiguous row blocks over
a 1-D ("agents",) mesh and executes each wave under shard_map: wave w
gathers only its *per-wave halo slab* — the read ∪ write rows of the
tasks at level w, split out of the window's halo at schedule time from
the model's ``task_read_agents``/``task_write_agents`` contracts —
instead of re-gathering the whole window halo (let alone all-gathering
the O(N) state); each device runs only the tasks whose write targets
fall in its rows and keeps its local block of the result. Recipes,
conflict matrix, wave levels, and the slab layout stay replicated —
they are window-local. The trajectory is asserted
bit-identical to the single-device wavefront engine and hence to
sequential execution — distribution, like wavefront scheduling itself,
is semantics-free.

Usage:  PYTHONPATH=src python examples/distributed_mabs.py
"""
import jax
import jax.numpy as jnp

from repro.core import ProtocolConfig, run_engine
from repro.mabs.voter import VoterModel
from repro.topology import watts_strogatz
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    print(f"devices: {len(jax.devices())}")
    model = VoterModel(watts_strogatz(1024, 4, 0.1, jax.random.key(0)))
    cfg = ProtocolConfig(window=256, strict=True)
    state0 = model.init_state(jax.random.key(0))

    ref, _ = run_engine(model, state0, 2_000, seed=1, config=cfg,
                        engine="wavefront")
    out, stats = run_engine(model, state0, 2_000, seed=1, config=cfg,
                            engine="sharded")

    same = bool(jnp.all(out["opinions"] == ref["opinions"]))
    print(f"sharded over {stats['n_devices']} devices; "
          f"mean wave parallelism {stats['mean_parallelism']:.1f}")
    print(f"halo exchange: {stats['halo']} "
          f"(per-wave split: {stats['halo_split']}) — per wave "
          f"{stats['per_wave_comm_bytes']} B/device gathered "
          f"(monolithic window halo {stats['window_halo_bytes']} B, "
          f"full state {stats['full_state_bytes']} B)")
    print(f"bit-identical to single-device trajectory: {same}")
    assert same
    print("OK")


if __name__ == "__main__":
    main()
