"""Multi-host bring-up tests: env contract, mesh-spec knob, and a real
2-process jax.distributed smoke run on CPU.

The reference has no multi-host path at all (SURVEY.md §2.3 row multi-host
SPMD); this framework's is TPU_WORKER_* env -> jax.distributed.initialize
(parallel/multihost.py), exercised here the way the k8s orchestration is
exercised with a fake apiserver: two real local processes, no cluster.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

from spotter_tpu.parallel import initialize_multihost, multihost_env_summary
from spotter_tpu.serving.app import parse_mesh_spec

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_single_host_is_noop(monkeypatch):
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    monkeypatch.delenv("TPU_WORKER_ID", raising=False)
    assert initialize_multihost() is False
    with pytest.raises(RuntimeError):
        initialize_multihost(force=True)


def test_one_hostname_never_joins_distributed(monkeypatch):
    """A TPU host may export TPU_WORKER_* for a single worker; one hostname
    is one host, so jax.distributed is not touched whatever they say."""
    import jax

    def must_not_run(**kwargs):
        raise AssertionError("jax.distributed.initialize on a single host")

    monkeypatch.setattr(jax.distributed, "initialize", must_not_run)
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    for hostnames in ("localhost", " t1v-n-0 ,", "10.0.0.2"):
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", hostnames)
        assert initialize_multihost() is False
        assert initialize_multihost(force=True) is False


def test_env_summary_contract(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
    summary = multihost_env_summary()
    assert summary["TPU_WORKER_ID"] == "1"
    assert summary["TPU_WORKER_HOSTNAMES"] == "h0,h1"
    assert summary["SPOTTER_COORDINATOR_PORT"]  # always has a default


def test_parse_mesh_spec():
    assert parse_mesh_spec("dp=4") == {"dp": 4, "tp": 1}
    assert parse_mesh_spec("dp=4,tp=2") == {"dp": 4, "tp": 2}
    assert parse_mesh_spec(" dp=2 , tp=1 ") == {"dp": 2, "tp": 1}
    for bad in ("", "tp=2", "dp=0", "dp=x", "pp=2,dp=2"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


_WORKER_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    from spotter_tpu.parallel import initialize_multihost

    assert initialize_multihost() is True
    import jax
    from jax.experimental import multihost_utils

    assert jax.process_count() == 2
    gathered = multihost_utils.process_allgather(
        np.array([jax.process_index()], np.int32)
    )
    assert sorted(int(v) for v in gathered.ravel()) == [0, 1], gathered
    print(f"worker {jax.process_index()} OK")
    """
)


@pytest.mark.slow
def test_two_process_distributed_smoke():
    """Two real processes join one jax.distributed cluster over localhost and
    run a cross-process allgather — the CPU stand-in for a 2-host DCN slice
    (VERDICT r1 item 4's 'done' criterion)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    procs = []
    for worker_id in range(2):
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            TPU_WORKER_ID=str(worker_id),
            TPU_WORKER_HOSTNAMES="127.0.0.1,127.0.0.1",
            SPOTTER_COORDINATOR_PORT=str(port),
            PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        # the virtual 8-device flag from conftest must not leak in: each
        # worker contributes its own (single-CPU-device) local runtime
        env["XLA_FLAGS"] = ""
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _WORKER_SCRIPT],
                env=env,
                cwd=REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"worker {i} OK" in out


_MESH_SERVE_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    from spotter_tpu.parallel import initialize_multihost

    assert initialize_multihost() is True
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    from spotter_tpu.parallel.mesh import make_mesh
    from spotter_tpu.parallel.sharding import data_sharding, replicated
    from spotter_tpu.serving.app import parse_mesh_spec

    assert jax.process_count() == 2
    assert len(jax.devices()) == 2  # one CPU device contributed per process

    # the exact serving bring-up order (serving/app.py build_detector_app):
    # initialize_multihost BEFORE make_mesh, spec via parse_mesh_spec
    axes = parse_mesh_spec("dp=2")
    mesh = make_mesh(dp=axes["dp"], tp=axes["tp"], source="test")
    in_sharding = data_sharding(mesh)

    # per-process batch shard -> global dp-sharded batch, exactly how the
    # engine places a bucket over the mesh (engine._in_sharding)
    local = np.full((2, 8), float(jax.process_index() + 1), np.float32)
    batch = jax.make_array_from_process_local_data(in_sharding, local, (4, 8))
    w = jax.device_put(np.eye(8, dtype=np.float32), replicated(mesh))

    @jax.jit
    def head(x, w):
        return jnp.tanh(x @ w).sum(axis=1)

    out = head(batch, w)
    got = sorted(
        round(float(v), 5)
        for shard in out.addressable_shards
        for v in np.asarray(shard.data).ravel()
    )
    want = sorted(
        round(float(np.tanh(jax.process_index() + 1)) * 8, 5)
        for _ in range(2)
    )
    assert got == want, (got, want)
    # and the cross-process view agrees: 2 rows of tanh(1)*8, 2 of tanh(2)*8
    all_rows = sorted(
        round(float(v), 5)
        for v in multihost_utils.process_allgather(np.asarray(got)).ravel()
    )
    expect = sorted(
        round(float(np.tanh(p + 1)) * 8, 5) for p in (0, 0, 1, 1)
    )
    assert all_rows == expect, (all_rows, expect)
    print(f"worker {jax.process_index()} MESH-SERVE OK")
    """
)


@pytest.mark.slow
def test_two_process_dp_mesh_serving_dryrun():
    """VERDICT r5 item 7: `initialize_multihost` + dp-mesh serving exercised
    TOGETHER — two real jax.distributed processes build one global dp=2 mesh
    through the serving bring-up path (parse_mesh_spec -> make_mesh ->
    data_sharding/replicated placement) and run a jitted sharded forward
    over a batch assembled from process-local shards. The 8-device dryrun
    is single-process; this is the cross-process half of config #5."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    procs = []
    for worker_id in range(2):
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            TPU_WORKER_ID=str(worker_id),
            TPU_WORKER_HOSTNAMES="127.0.0.1,127.0.0.1",
            SPOTTER_COORDINATOR_PORT=str(port),
            PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        env["XLA_FLAGS"] = ""  # one device per worker, no virtual mesh
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _MESH_SERVE_SCRIPT],
                env=env,
                cwd=REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"worker {i} MESH-SERVE OK" in out
