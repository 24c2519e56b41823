"""Multi-process distributed runtime: env contract + JAX bootstrap.

TPU-native replacement for the reference's NCCL2 bootstrap path: the
``gen_nccl_id`` op's TCP exchange of ncclUniqueId
(reference: paddle/fluid/operators/distributed_ops/gen_nccl_id_op.cc:162) and
the transpiler's nccl2 mode (transpiler/distribute_transpiler.py:308) collapse
into one ``jax.distributed.initialize`` call; XLA then runs collectives over
ICI/DCN directly. The PADDLE_* environment contract is kept verbatim from the
reference launcher (python/paddle/distributed/launch.py:147) so reference
cluster tooling works unchanged:

  PADDLE_TRAINER_ID         this process's rank            (int)
  PADDLE_TRAINERS_NUM       world size                     (int)
  PADDLE_CURRENT_ENDPOINT   this process's ip:port
  PADDLE_TRAINER_ENDPOINTS  comma-separated all endpoints; [0] doubles as the
                            jax.distributed coordinator address
  PADDLE_DIST_BACKEND       optional: "cpu" forces the CPU backend with gloo
                            collectives (multi-host simulation on one host);
                            unset -> real TPU backend
  PADDLE_LOCAL_DEVICES      optional: devices per process on the cpu backend
"""
from __future__ import annotations

import os
from typing import List, Optional

__all__ = ["ParallelEnv", "init_parallel_env", "get_rank", "get_world_size",
           "is_initialized", "barrier", "all_gather_object"]


class ParallelEnv:
    """Reference dygraph/parallel.py:54 Env: the cluster env-var view."""

    def __init__(self):
        self.trainer_id = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self.nranks = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
        self.current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")
        eps = os.getenv("PADDLE_TRAINER_ENDPOINTS", "")
        self.trainer_endpoints: List[str] = [e for e in eps.split(",") if e]
        self.backend = os.getenv("PADDLE_DIST_BACKEND", "")
        self.local_devices = int(os.getenv("PADDLE_LOCAL_DEVICES", "0"))

    @property
    def rank(self) -> int:
        return self.trainer_id

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def dev_id(self) -> int:
        return int(os.getenv("FLAGS_selected_tpus",
                             os.getenv("FLAGS_selected_gpus", "0")))


_initialized = False


def is_initialized() -> bool:
    return _initialized


def init_parallel_env(coordinator_address: Optional[str] = None) -> ParallelEnv:
    """Bootstrap the multi-process runtime from the PADDLE_* env contract.

    Single-process (PADDLE_TRAINERS_NUM absent or 1) is a no-op, so the same
    training script runs standalone or under the launcher — the reference's
    transpile-if-distributed pattern without the transpiler.

    Must run before any JAX computation (backend init freezes the topology,
    like NCCL comm init in the reference).
    """
    global _initialized
    env = ParallelEnv()
    if env.nranks <= 1 or _initialized:
        return env

    import jax

    if env.backend == "cpu":
        # multi-host simulation: CPU backend, gloo collectives over TCP
        backends = getattr(jax._src.xla_bridge, "_backends", None)
        if backends:
            raise RuntimeError(
                "init_parallel_env must run before JAX initializes a backend")
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", env.local_devices or 1)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coord = coordinator_address or (
        env.trainer_endpoints[0] if env.trainer_endpoints else None)
    if coord is None:
        raise RuntimeError(
            "init_parallel_env: no coordinator — set PADDLE_TRAINER_ENDPOINTS "
            "or pass coordinator_address")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=env.nranks,
                               process_id=env.trainer_id)
    _initialized = True
    return env


def get_rank() -> int:
    return ParallelEnv().trainer_id


def get_world_size() -> int:
    return ParallelEnv().nranks


def barrier() -> None:
    """Host-level sync via the coordination service (reference: the barrier
    semantics of listen_and_serv's RunSyncLoop, minus the parameter server)."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("paddle_tpu_barrier")


def all_gather_object(arr):
    """Gather a numpy array from every process; returns a list indexed by
    rank (debug/metrics aggregation across trainers)."""
    import jax
    import numpy as np

    if jax.process_count() <= 1:
        return [np.asarray(arr)]
    from jax.experimental import multihost_utils

    stacked = multihost_utils.process_allgather(np.asarray(arr))
    return [np.asarray(s) for s in stacked]


def allgather_mean_tree(tree: dict) -> dict:
    """Average a {key: ndarray} tree across processes in ONE collective
    (identity single-process). Shared by LocalSGD and dygraph DataParallel
    — the coalesced-allreduce primitive of the reference's collective
    transpiler."""
    import jax
    import numpy as np

    if jax.process_count() <= 1:
        return dict(tree)
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        {k: np.asarray(v) for k, v in tree.items()}, tiled=False)
    return {k: jax.numpy.asarray(np.mean(np.asarray(gathered[k]), axis=0))
            for k in tree}
