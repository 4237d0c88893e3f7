"""Multi-host (pod / multi-node) wiring.

The reference's multi-node story is an id-rendezvous + NCCL communicator
per rank (gen_nccl_id_op.cc:31, platform/nccl_helper.h:130, nranks =
num_trainers x ndev, parallel_executor.cc:203) or gRPC parameter servers.
TPU-native replacement: `jax.distributed.initialize` joins every host into
ONE runtime; jax.devices() then spans the pod, a Mesh built over them spans
hosts, and the SAME SPMD program runs everywhere — GSPMD collectives ride
ICI within a slice and DCN across hosts. No id exchange, no pserver role.

Cluster env contract follows the reference's
(transpiler/distribute_transpiler.py:222 nccl2 mode / test_dist_base.py):
  PADDLE_TRAINERS            number of processes (trainer count)
  PADDLE_TRAINER_ID          this process's rank
  PADDLE_TRAINER_ENDPOINTS   comma list host:port; entry 0 is the
                             coordinator (or set PADDLE_COORDINATOR)
"""
from __future__ import annotations

import os

import numpy as np
import jax

_initialized = {'done': False}


def _effective_platform(platform):
    """The platform the backend will initialize with: the explicit
    argument wins, then JAX_PLATFORMS."""
    if platform is not None:
        return platform
    v = os.environ.get('JAX_PLATFORMS')
    return v.split(',')[0] if v else None


def init_distributed(coordinator_address=None, num_trainers=None,
                     trainer_id=None, platform=None):
    """Join this process into the multi-host runtime. No-op for a single
    trainer. Call before any other jax use (backends must not be
    initialized yet). Returns (num_trainers, trainer_id)."""
    if num_trainers is None:
        num_trainers = int(os.environ.get('PADDLE_TRAINERS', '1'))
    if trainer_id is None:
        trainer_id = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    if coordinator_address is None:
        coordinator_address = os.environ.get('PADDLE_COORDINATOR')
    if coordinator_address is None:
        eps = os.environ.get('PADDLE_TRAINER_ENDPOINTS', '')
        if eps:
            coordinator_address = eps.split(',')[0]
    if platform is not None:
        # pin the platform BEFORE backend init (e.g. 'cpu' for the
        # simulated-pod tests; on a real pod the TPU platform is
        # default). Also on the single-trainer path: an elastic pod
        # resized down to ONE host runs the same worker script, and
        # skipping the pin there would let an installed TPU plugin
        # initialize (and hang on GCP metadata) despite the explicit
        # platform argument.
        jax.config.update('jax_platforms', platform)
    if num_trainers <= 1:
        return 1, 0
    if coordinator_address is None:
        raise ValueError(
            "multi-host init needs a coordinator: set PADDLE_COORDINATOR or "
            "PADDLE_TRAINER_ENDPOINTS (first endpoint is the coordinator)")
    if _effective_platform(platform) == 'cpu':
        # XLA:CPU alone cannot execute a computation spanning processes
        # ("Multiprocess computations aren't implemented on the CPU
        # backend"); gloo supplies the cross-process collective transport
        # for the simulated pod. Must land BEFORE backend init.
        try:
            jax.config.update('jax_cpu_collectives_implementation', 'gloo')
        except Exception:
            pass    # jaxlib without gloo: single-host-per-program only
    if not _initialized['done']:
        jax.distributed.initialize(coordinator_address,
                                   num_processes=num_trainers,
                                   process_id=trainer_id)
        _initialized['done'] = True
    return num_trainers, trainer_id


def process_count():
    try:
        return jax.process_count()
    except RuntimeError:
        return 1


def process_index():
    try:
        return jax.process_index()
    except RuntimeError:
        return 0


def mesh_spans_processes(mesh):
    devs = np.asarray(mesh.devices).reshape(-1)
    return len({d.process_index for d in devs}) > 1


def pod_run_id():
    """One id shared by every process of THIS pod incarnation — the token
    PodCheckpointManager uses to keep a restarted pod from stitching a
    dead incarnation's stale host shards into a fresh checkpoint.
    Resolution order: PTPU_POD_RUN_ID (set by the pod supervisor /
    tools/chaos.py --pod), else rank 0 mints a uuid and shares it through
    the distributed KV store, else (single process) a local uuid."""
    rid = os.environ.get('PTPU_POD_RUN_ID')
    if rid:
        return rid
    import uuid
    if process_count() <= 1:
        return uuid.uuid4().hex
    try:
        client = jax._src.distributed.global_state.client
        if process_index() == 0:
            rid = uuid.uuid4().hex
            client.key_value_set('ptpu_pod_run_id', rid)
            return rid
        return client.blocking_key_value_get('ptpu_pod_run_id', 60_000)
    except Exception as e:
        # no KV store (older jaxlib): there is NO way to mint a token
        # that is both shared across hosts and unique per incarnation —
        # a coordinator-address fallback would repeat across restarts
        # and re-open the exact stale-shard stitching hole the run_id
        # exists to close. Make the operator supply one.
        raise RuntimeError(
            'pod_run_id: no distributed KV store available (%s: %s) — '
            'set PTPU_POD_RUN_ID to a fresh value for every pod launch'
            % (type(e).__name__, e))


# pod-scale failure-detection primitives live next to the checkpoint
# machinery (stdlib-only, standalone-loadable by tools/chaos.py); re-export
# the parallel-facing surface here
from ..core.checkpoint import (     # noqa: E402,F401
    BarrierTimeout, fs_barrier, write_heartbeat, read_heartbeats,
    stale_hosts, HostWatchdog, PodCheckpointManager, pod_latest_committed)


def place_local_shard(sharding, local_np, n_processes):
    """Assemble a GLOBAL array from this process's local batch shard
    (the TPU equivalent of each trainer feeding its own data shard,
    test_dist_base methodology). The global leading dim is
    local_rows x n_processes; sharded dims must divide accordingly."""
    local_np = np.asarray(local_np)
    global_shape = (local_np.shape[0] * n_processes,) + local_np.shape[1:]
    return jax.make_array_from_process_local_data(sharding, local_np,
                                                  global_shape=global_shape)
