"""Backend plugins: per-framework worker-group wiring.

Parity: reference train/backend.py:32-56 (Backend ABC with
on_start/on_training_start/on_shutdown) and the torch-XLA backend's
master-address broadcast + env fanout (train/torch/xla/config.py:120-169),
re-done for JAX: worker 0 donates a coordinator address and every worker
joins via jax.distributed.initialize — after which each worker's
jax.devices() is the global pod view and pjit/shard_map span all hosts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from ray_tpu.train.worker_group import WorkerGroup


@dataclasses.dataclass
class BackendConfig:
    def backend_cls(self):
        return Backend


class Backend:
    """No-op base backend."""

    def on_start(self, worker_group: WorkerGroup,
                 backend_config: "BackendConfig") -> None:
        pass

    def on_training_start(self, worker_group: WorkerGroup,
                          backend_config: "BackendConfig") -> None:
        pass

    def on_shutdown(self, worker_group: WorkerGroup) -> None:
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """distributed=True joins all workers into one jax.distributed
    runtime (required for multi-host SPMD; off for independent workers
    and single-worker groups). `env` is fanned out to every worker
    before its first jax import (XLA_FLAGS and the like). The platform
    is not a setting: a worker granted chips (ScalingConfig.use_tpu)
    runs JAX on `tpu` with exactly those chips visible, any other
    worker on `cpu` — the scheduler's grant decides, see
    _private/accelerators/tpu.py:apply_chip_grant."""
    distributed: Optional[bool] = None  # None = auto (W > 1)
    coordinator_port: Optional[int] = None
    env: Optional[dict] = None

    def backend_cls(self):
        return JaxBackend


def _join_distributed(coordinator: str, num_processes: int, rank: int):
    import jax
    from ray_tpu.parallel.dist import initialize_distributed
    initialize_distributed(coordinator, num_processes, rank)
    return jax.process_index()


class JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup,
                 backend_config: JaxConfig) -> None:
        import cloudpickle

        import ray_tpu
        w = worker_group.num_workers
        distributed = backend_config.distributed
        if distributed is None:
            distributed = w > 1
        if backend_config.env:
            worker_group.set_env_on_all(backend_config.env)
        if not distributed:
            return
        addr = ray_tpu.get(worker_group.workers[0].get_address.remote())
        port = (backend_config.coordinator_port
                or ray_tpu.get(
                    worker_group.workers[0].find_free_port.remote()))
        coordinator = f"{addr}:{port}"
        # every worker joins; worker 0 hosts the coordinator service
        join = cloudpickle.dumps(_join_distributed)
        refs = [worker_group.workers[rank].run.remote(
            join, (coordinator, w, rank), {})
            for rank in range(w)]
        ray_tpu.get(refs, timeout=120)
