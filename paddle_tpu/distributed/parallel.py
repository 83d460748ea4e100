"""Process/runtime env. ≙ reference `init_parallel_env` + TCPStore rendezvous
(«paddle/phi/core/distributed/store/tcp_store.cc», fleet launch env vars [U]).

TPU-native: `jax.distributed.initialize` (coordinator service) replaces
TCPStore; one process per host, all chips of the host attached to it. Rank =
process_index, world = process_count. On a single host this is trivially a
no-op and the 'world' is the local chip set."""
from __future__ import annotations

import os

import jax

_initialized = False


def init_parallel_env():
    """≙ paddle.distributed.init_parallel_env. Reads the same env-var shape
    the reference launcher sets (PADDLE_TRAINER_ID etc. become
    COORDINATOR/NUM_PROCESSES/PROCESS_ID)."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_MASTER") or os.environ.get(
        "COORDINATOR_ADDRESS")
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM",
                                os.environ.get("NUM_PROCESSES", "1")))
    pid = int(os.environ.get("PADDLE_TRAINER_ID",
                             os.environ.get("PROCESS_ID", "0")))
    if coord and nprocs > 1:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nprocs, process_id=pid)
    _initialized = True
    return ParallelEnv()


def get_rank(group=None) -> int:
    if group is not None:
        return group.rank
    return jax.process_index()


def get_world_size(group=None) -> int:
    if group is not None:
        return group.nranks
    return jax.process_count()


def is_initialized() -> bool:
    return _initialized


def is_available() -> bool:
    return True


class ParallelEnv:
    """≙ paddle.distributed.ParallelEnv."""

    @property
    def rank(self) -> int:
        return jax.process_index()

    @property
    def world_size(self) -> int:
        return jax.process_count()

    @property
    def local_rank(self) -> int:
        return 0  # one process per host on TPU; chips are in-process

    @property
    def device_id(self) -> int:
        return 0

    @property
    def nranks(self) -> int:
        return self.world_size

    @property
    def dev_id(self) -> int:
        return 0


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """≙ paddle.distributed.spawn («python/paddle/distributed/spawn.py»
    [U]): fork `nprocs` worker processes, each with the launcher's env-var
    shape (PADDLE_TRAINER_ID/..., a shared coordinator port) and run
    `func(*args)` in every rank. On this TPU-native stack each worker is
    one jax process; `init_parallel_env()` inside `func` joins them via
    jax.distributed. Workers inherit JAX_PLATFORMS (tests use cpu).

    Returns the list of exit codes when join=True (raises on nonzero),
    else the list of Process handles.
    """
    import multiprocessing as mp
    import socket

    if nprocs <= 0:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if nprocs <= 0:
        nprocs = 1
    if nprocs > 1 and jax.devices()[0].platform == "tpu":
        # a chip belongs to one process: this parent already holds the
        # local chips, and each of the N children would want them all
        # again — they would fail or hang at backend start-up
        raise RuntimeError(
            f"spawn(nprocs={nprocs}) on a TPU host: every worker would "
            "claim the chips this process already holds. One process "
            "drives all local chips (dist.create_mesh); use "
            "paddle_tpu.distributed.launch for one process per host")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    master = f"127.0.0.1:{port}"

    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_worker,
                        args=(func, args, master, nprocs, rank),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if not join:
        return procs
    codes = []
    for p in procs:
        p.join()
        codes.append(p.exitcode)
    if any(codes):
        raise RuntimeError(f"spawn: worker exit codes {codes}")
    return codes


def _spawn_worker(func, args, master, nprocs, rank):
    os.environ["PADDLE_MASTER"] = master
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    init_parallel_env()
    func(*args)
