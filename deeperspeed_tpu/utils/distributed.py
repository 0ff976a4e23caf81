"""Distributed init (reference: `deepspeed/utils/distributed.py:12`).

`torch.distributed.init_process_group` becomes
`jax.distributed.initialize`: one process per host, all chips addressed
through the mesh. Rendezvous from env vars (MASTER_ADDR/PORT, RANK,
WORLD_SIZE — same names the reference launcher exports) or MPI discovery
via mpi4py when requested.
"""

import os
import time

import jax

from .logging import logger

_initialized = False

# Default deadline (seconds) for host-coordination barriers; None waits
# forever (the seed's behavior). Set via init_distributed(timeout=...) —
# a dead host then fails the BARRIER fast instead of hanging every
# surviving host until the scheduler gives up.
_collective_timeout = None
_barrier_serials = {}
_warned_no_client = False

# Coordination-service barriers are ALWAYS deadline-bearing when the
# client exists: with no timeout configured this default applies instead
# of degrading to the unbounded device-collective fallback. A dead peer
# then surfaces as a typed BarrierTimeoutError after this many seconds
# — still far faster (and infinitely more diagnosable) than an infinite
# sync_global_devices hang.
DEFAULT_BARRIER_TIMEOUT_S = 900.0

# fault-injection seam (runtime/fault_injection.py `barrier_timeout`
# faults): {tag_or_None: remaining_fires}. None matches any tag.
_forced_timeouts = {}


class BarrierTimeoutError(RuntimeError):
    """A host-coordination barrier blew its deadline: one or more peers
    never arrived (dead, preempted, or wedged). Carries the barrier tag
    and the elapsed wall time so the supervisor / logs can tell WHICH
    rendezvous failed and how long the survivors waited."""

    def __init__(self, tag, timeout_s, elapsed_s, cause=None):
        self.tag = tag
        self.timeout_s = float(timeout_s)
        self.elapsed_s = float(elapsed_s)
        super().__init__(
            f"barrier '{tag}' timed out after {elapsed_s:.1f}s "
            f"(deadline {timeout_s:.1f}s): a peer host never arrived"
            + (f" — {cause}" if cause else ""))


def inject_barrier_timeout(tag=None, times=1):
    """Arm the next `times` barrier call(s) (optionally only those with
    `tag`) to raise BarrierTimeoutError without waiting — the
    single-host test seam for the `barrier_timeout` fault kind."""
    _forced_timeouts[tag] = _forced_timeouts.get(tag, 0) + int(times)


def _pop_forced_timeout(tag):
    for key in (tag, None):
        if _forced_timeouts.get(key, 0) > 0:
            _forced_timeouts[key] -= 1
            if not _forced_timeouts[key]:
                del _forced_timeouts[key]
            return True
    return False


def get_collective_timeout():
    """The barrier/collective deadline configured via
    init_distributed(timeout=...), in seconds (None = wait forever)."""
    return _collective_timeout


def init_distributed(dist_backend="xla", auto_mpi_discovery=True,
                     distributed_port=29500, verbose=True,
                     timeout=None, init_method=None):
    """Join the multi-host world if env/MPI rendezvous info is present;
    single-host runs are a no-op (all local chips already visible).

    `timeout` (seconds) bounds BOTH the rendezvous
    (`jax.distributed.initialize(initialization_timeout=...)`) and every
    later `barrier()` call — a dead host fails fast instead of hanging
    the fleet forever."""
    global _initialized, _collective_timeout
    if timeout is not None:
        # recorded even on the early-return paths: barrier() must honor
        # the caller's deadline regardless of when the world formed
        _collective_timeout = float(timeout)
    if _initialized:
        return

    _patch_azureml_env(verbose=verbose)

    required_env = ["RANK", "WORLD_SIZE", "MASTER_ADDR"]
    if auto_mpi_discovery and \
            not all(v in os.environ for v in required_env) and \
            "OMPI_COMM_WORLD_SIZE" in os.environ:
        mpi_discovery(distributed_port=distributed_port, verbose=verbose)

    world_size = int(os.environ.get("WORLD_SIZE", "1"))
    if world_size <= 1:
        _initialized = True
        return

    rank = int(os.environ.get("RANK", "0"))
    addr = os.environ.get("MASTER_ADDR", "127.0.0.1")
    port = os.environ.get("MASTER_PORT", str(distributed_port))
    if verbose:
        logger.info(
            f"Initializing jax.distributed: rank={rank}, "
            f"world_size={world_size}, coordinator={addr}:{port}"
            + (f", timeout={timeout}s" if timeout is not None else ""))
    kwargs = {}
    if timeout is not None:
        kwargs["initialization_timeout"] = int(float(timeout))
    jax.distributed.initialize(
        coordinator_address=f"{addr}:{port}",
        num_processes=world_size,
        process_id=rank, **kwargs)
    _initialized = True


def _distributed_client():
    try:
        from jax._src import distributed
        return distributed.global_state.client
    except Exception:  # pragma: no cover - private-API drift
        return None


def barrier(tag, timeout=None):
    """Multihost host-level barrier with a fail-fast deadline.

    Whenever a coordination client exists the barrier runs on the
    coordination service (`wait_at_barrier`) under a deadline — the
    explicit `timeout` argument, the `init_distributed(timeout=...)`
    default, or `DEFAULT_BARRIER_TIMEOUT_S` as the floor — and a missing
    host raises a typed `BarrierTimeoutError` (tag + elapsed) instead of
    the raw gRPC DEADLINE_EXCEEDED: a preempted/dead peer costs seconds
    and is diagnosable, not an infinite hang inside a device collective.

    HAZARD: the `sync_global_devices` fallback (no client — single
    controller, or jax builds without the client API) is a DEVICE
    collective with NO deadline of any kind: a dead peer hangs every
    surviving host until the cluster scheduler reaps the job. It is kept
    only as a last resort; callers that need fail-fast semantics must
    run under `jax.distributed.initialize` (the launcher's default).
    Single-process: no-op (forced-timeout injection still fires, so the
    fault-injection harness can drive the failure path on one host)."""
    if jax.process_count() <= 1 and not _forced_timeouts:
        return
    timeout = _collective_timeout if timeout is None else timeout
    if _pop_forced_timeout(tag):
        raise BarrierTimeoutError(
            tag, timeout or DEFAULT_BARRIER_TIMEOUT_S, 0.0,
            cause="injected fault (barrier_timeout)")
    if jax.process_count() <= 1:
        return
    client = _distributed_client()
    if client is not None:
        # the client path is ALWAYS deadline-bearing: an unbounded
        # coordination wait would just reproduce the device-collective
        # hang with extra steps
        timeout = float(timeout) if timeout else DEFAULT_BARRIER_TIMEOUT_S
        # wait_at_barrier ids must be unique per rendezvous; every
        # host derives the same serial for the same call site order
        serial = _barrier_serials.get(tag, 0)
        _barrier_serials[tag] = serial + 1
        t0 = time.monotonic()
        try:
            client.wait_at_barrier(f"{tag}:{serial}",
                                   int(timeout * 1000))
        except Exception as e:
            elapsed = time.monotonic() - t0
            # DEADLINE_EXCEEDED from a missing peer; re-raise typed so
            # callers (checkpoint commit, supervisor handoff) can tell a
            # barrier timeout from a generic runtime error
            if "DEADLINE" in str(e).upper() or elapsed >= timeout * 0.9:
                raise BarrierTimeoutError(tag, timeout, elapsed,
                                          cause=e) from e
            raise
        return
    if timeout:
        global _warned_no_client
        if not _warned_no_client:  # pragma: no cover - env dependent
            _warned_no_client = True
            logger.warning("barrier timeout requested but no distributed "
                           "client is available; falling back to the "
                           "UNBOUNDED device-collective barrier (a dead "
                           "peer will hang this job until the scheduler "
                           "reaps it)")
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(tag)


def _patch_azureml_env(verbose=True):
    """Map AzureML's OpenMPI env vars onto the standard rendezvous vars
    (reference `distributed.py`'s in_aml()/patch_aml_env path)."""
    if "AZUREML_EXPERIMENT_ID" not in os.environ:
        return
    if "OMPI_COMM_WORLD_RANK" not in os.environ:
        return
    os.environ.setdefault("RANK", os.environ["OMPI_COMM_WORLD_RANK"])
    os.environ.setdefault("WORLD_SIZE",
                          os.environ.get("OMPI_COMM_WORLD_SIZE", "1"))
    os.environ.setdefault("LOCAL_RANK",
                          os.environ.get("OMPI_COMM_WORLD_LOCAL_RANK", "0"))
    if int(os.environ["WORLD_SIZE"]) == 1:
        os.environ.setdefault("MASTER_ADDR", "127.0.0.1")
    else:
        master = os.environ.get("AZ_BATCH_MASTER_NODE") or \
            os.environ.get("AZ_BATCHAI_MPI_MASTER_NODE")
        if not master:
            raise RuntimeError(
                "AzureML multi-node job but neither AZ_BATCH_MASTER_NODE "
                "nor AZ_BATCHAI_MPI_MASTER_NODE is set — cannot determine "
                "the rendezvous address (a localhost default would make "
                "every node rendezvous with itself)")
        addr, _, port = master.partition(":")
        os.environ.setdefault("MASTER_ADDR", addr)
        if port:
            os.environ.setdefault("MASTER_PORT", port)
    if verbose:
        logger.info("Detected AzureML environment; patched rendezvous "
                    "env vars from OMPI settings")


def mpi_discovery(distributed_port=29500, verbose=True):
    """Discover rank/world/master from MPI and export the standard env vars
    (reference `distributed.py:54`)."""
    from mpi4py import MPI

    comm = MPI.COMM_WORLD
    rank = comm.Get_rank()
    world_size = comm.Get_size()

    import socket
    master_addr = None
    if rank == 0:
        master_addr = socket.gethostbyname(socket.gethostname())
    master_addr = comm.bcast(master_addr, root=0)

    os.environ["RANK"] = str(rank)
    os.environ["WORLD_SIZE"] = str(world_size)
    os.environ["MASTER_ADDR"] = master_addr
    os.environ["MASTER_PORT"] = str(distributed_port)
    os.environ["LOCAL_RANK"] = str(
        comm.Split_type(MPI.COMM_TYPE_SHARED).Get_rank())

    if verbose:
        logger.info(
            f"MPI discovery: rank={rank}, world_size={world_size}, "
            f"master_addr={master_addr}, master_port={distributed_port}")
