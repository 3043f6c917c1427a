"""Utility-layer tests: global norm, watchdog, discovery parsing."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from stochastic_gradient_push_tpu.parallel.discovery import (
    ClusterInfo,
    _first_slurm_host,
    discover,
)
from stochastic_gradient_push_tpu.utils import StepWatchdog, global_norm


def test_global_norm():
    tree = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    np.testing.assert_allclose(float(global_norm(tree)), 5.0)


def test_watchdog_fires_on_slow_step_and_not_on_fast():
    wd = StepWatchdog(timeout=0.2)
    with wd.step():
        pass
    time.sleep(0.3)
    assert not wd.timed_out

    wd2 = StepWatchdog(timeout=0.1)
    with wd2.step():
        time.sleep(0.35)
    assert wd2.timed_out


def test_discover_reports_cpu_mesh():
    info = discover()
    assert isinstance(info, ClusterInfo)
    assert info.platform == "cpu"
    assert info.global_device_count >= 8
    assert not info.is_multihost


def test_slurm_nodelist_first_host():
    assert _first_slurm_host("tpu-pod-[003-007,010]") == "tpu-pod-003"
    assert _first_slurm_host("a-1,b-2") == "a-1"
    assert _first_slurm_host("node[001-004]") == "node001"
    assert _first_slurm_host("single") == "single"


def _captured_initialize(monkeypatch):
    """Stub jax.distributed.initialize and return the capture dict."""
    got = {}

    def fake_init(coordinator_address=None, num_processes=None,
                  process_id=None):
        got.update(coordinator_address=coordinator_address,
                   num_processes=num_processes, process_id=process_id)

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    return got


def test_mpi_env_bootstrap(monkeypatch):
    """OpenMPI launcher env (reference --backend mpi, gossip_sgd.py:600-602)
    derives rank/size; COORDINATOR_ADDRESS wins over HOSTNAME."""
    from stochastic_gradient_push_tpu.parallel.discovery import (
        initialize_multihost)

    for var in ("SLURM_PROCID", "SLURM_NTASKS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    monkeypatch.setenv("COORDINATOR_ADDRESS", "head-node:40123")
    got = _captured_initialize(monkeypatch)
    initialize_multihost()
    assert got == {"coordinator_address": "head-node:40123",
                   "num_processes": 4, "process_id": 3}

    # reference fallbacks: OMPI_UNIVERSE_SIZE for world, HOSTNAME for the
    # coordinator, default port appended to a bare host
    monkeypatch.delenv("OMPI_COMM_WORLD_SIZE")
    monkeypatch.delenv("COORDINATOR_ADDRESS")
    monkeypatch.setenv("OMPI_UNIVERSE_SIZE", "8")
    monkeypatch.setenv("HOSTNAME", "mpi-head")
    got = _captured_initialize(monkeypatch)
    initialize_multihost()
    assert got == {"coordinator_address": "mpi-head:40100",
                   "num_processes": 8, "process_id": 3}


def test_mpi_multinode_without_coordinator_fails_fast(monkeypatch):
    """A multi-node mpirun with no COORDINATOR_ADDRESS must raise, not
    let every rank dial its own hostname and hang in initialize."""
    import pytest

    from stochastic_gradient_push_tpu.parallel.discovery import (
        initialize_multihost)

    import socket

    for var in ("SLURM_PROCID", "SLURM_NTASKS", "COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.delenv("HOSTNAME", raising=False)
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "2")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_SIZE", "2")
    _captured_initialize(monkeypatch)
    with pytest.raises(RuntimeError, match="COORDINATOR_ADDRESS"):
        initialize_multihost()

    # env HOSTNAME == this machine's own name: still a self-dial → raise
    monkeypatch.setenv("HOSTNAME", socket.gethostname())
    with pytest.raises(RuntimeError, match="COORDINATOR_ADDRESS"):
        initialize_multihost()

    # mpirun -x HOSTNAME: rank 0's hostname propagated to a remote node
    # differs from the machine's own name → trusted as the coordinator
    monkeypatch.setenv("HOSTNAME", "head-node-from-rank0")
    got = _captured_initialize(monkeypatch)
    initialize_multihost()
    assert got["coordinator_address"] == "head-node-from-rank0:40100"

    # single-node (local size == world size): HOSTNAME fallback is fine
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_SIZE", "4")
    monkeypatch.setenv("HOSTNAME", "onebox")
    got = _captured_initialize(monkeypatch)
    initialize_multihost()
    assert got["coordinator_address"] == "onebox:40100"


def test_slurm_env_wins_over_mpi(monkeypatch):
    """When both schedulers' vars are present, SLURM keeps priority (the
    reference selects by --backend; auto-detection must be deterministic)."""
    from stochastic_gradient_push_tpu.parallel.discovery import (
        initialize_multihost)

    monkeypatch.setenv("SLURM_PROCID", "1")
    monkeypatch.setenv("SLURM_NTASKS", "2")
    monkeypatch.setenv("SLURM_JOB_NODELIST", "single")
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "7")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "9")
    got = _captured_initialize(monkeypatch)
    initialize_multihost()
    assert got["process_id"] == 1
    assert got["num_processes"] == 2


def test_mpi_env_multihost_autodetect(monkeypatch):
    from stochastic_gradient_push_tpu.run.gossip_sgd import _multihost_env

    for var in ("JAX_COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES",
                "SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE",
                "OMPI_UNIVERSE_SIZE"):
        monkeypatch.delenv(var, raising=False)
    assert not _multihost_env()
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "2")
    assert _multihost_env()


def test_profiler_guard_times_out_without_hanging():
    """The profiler guard (utils/profiling.py): a hung profiler call
    must return False within the timeout instead of stalling the run."""
    import time

    from stochastic_gradient_push_tpu.utils.profiling import (
        _call_with_timeout)

    t0 = time.monotonic()
    ok = _call_with_timeout(lambda: time.sleep(30), timeout=0.2,
                            what="test")
    assert not ok
    assert time.monotonic() - t0 < 5

    # a fast call passes through, and its exception surfaces
    assert _call_with_timeout(lambda: None, timeout=5, what="test")
    import pytest

    with pytest.raises(RuntimeError):
        _call_with_timeout(
            lambda: (_ for _ in ()).throw(RuntimeError("x")),
            timeout=5, what="test")


def test_profiler_guard_late_completion_callback():
    """A call declared hung that later completes must trigger the
    compensating callback (e.g. stopping a late-started trace)."""
    import threading
    import time

    from stochastic_gradient_push_tpu.utils.profiling import (
        _call_with_timeout)

    compensated = threading.Event()
    ok = _call_with_timeout(lambda: time.sleep(0.5), timeout=0.1,
                            what="test",
                            on_late_completion=compensated.set)
    assert not ok
    assert compensated.wait(5), "late completion never compensated"
