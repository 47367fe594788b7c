"""Local multi-replica harness: supervised stub replicas as subprocesses.

The failover acceptance tests (tests/test_failover.py) and the controller
drills need the same fixture: N REAL server processes (the
standalone aiohttp runtime, stub engine, full lifecycle surface) each under
the REAL supervisor, on localhost ports, killable mid-load — the CPU
stand-in for a spot TPU fleet losing a host. This module is that fixture.

Hermeticity mirrors tests/test_multihost.py: the spawned processes must not
inherit the virtual-device XLA flag, and always run JAX_PLATFORMS=cpu.
"""

import os
import signal
import socket
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def pick_ports(n: int) -> list[int]:
    """Ephemeral localhost ports (bound briefly, then released)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _hermetic_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="",
        SPOTTER_TPU_STUB_ENGINE="1",
        PYTHONPATH=REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    )
    for var in ("SPOTTER_TPU_FAULTS", "SPOTTER_TPU_ADMIN_TOKEN"):
        env.pop(var, None)
    if extra:
        env.update(extra)
    return env


class SupervisedReplica:
    """One supervisor subprocess running one stub standalone server."""

    def __init__(
        self,
        port: int,
        pidfile: str,
        backoff_base_s: float = 0.2,
        min_uptime_s: float = 0.5,
        env: dict | None = None,
        manifest: str | None = None,
    ) -> None:
        self.port = port
        self.url = f"http://127.0.0.1:{port}"
        self.pidfile = pidfile
        self.manifest = manifest
        # file-backed output, NOT a pipe: nothing drains a pipe until
        # shutdown(), so a long-lived member (health probes log every poll)
        # would fill the 64 KB pipe buffer and block the server on a stdout
        # write — a "healthy" replica that suddenly stops answering /healthz
        self.log_path = pidfile + ".log"
        self._log_file = open(self.log_path, "w")
        cmd = [
            sys.executable, "-m", "spotter_tpu.serving.supervisor",
            "--backoff-base", str(backoff_base_s),
            "--min-uptime", str(min_uptime_s),
            "--pidfile", pidfile,
        ]
        if manifest:
            # ISSUE 16: the supervisor self-registers in the endpoints
            # manifest so a (re)started controller can adopt this member
            cmd += ["--manifest", manifest, "--url", self.url]
        cmd += [
            "--",
            sys.executable, "-m", "spotter_tpu.serving.standalone",
            "--stub-engine", "--no-warmup",
            "--host", "127.0.0.1", "--port", str(port),
        ]
        self.proc = subprocess.Popen(
            cmd,
            env=_hermetic_env(env),
            cwd=REPO_ROOT,
            stdout=self._log_file,
            stderr=subprocess.STDOUT,
            text=True,
        )

    def child_pid(self) -> int | None:
        try:
            with open(self.pidfile) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return None

    def kill_child(self, sig: int = signal.SIGKILL) -> int:
        """The preemption fault: kill the SERVER (the supervisor stays and
        must restart it). Returns the killed pid."""
        pid = self.child_pid()
        if pid is None:
            raise RuntimeError(f"no child pid recorded in {self.pidfile}")
        os.kill(pid, sig)
        return pid

    def shutdown(self, timeout_s: float = 10.0) -> str:
        """SIGTERM the supervisor (it forwards to the child) and collect
        output."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._log_file.close()
        try:
            with open(self.log_path) as f:
                return f.read()
        except OSError:
            return ""


def wait_ready(url: str, timeout_s: float = 60.0, interval_s: float = 0.1) -> float:
    """Block until `url`/startupz answers 200; returns seconds waited.
    Raises TimeoutError with the last observed state on expiry."""
    import httpx

    t0 = time.monotonic()
    last = "no answer yet"
    while time.monotonic() - t0 < timeout_s:
        try:
            resp = httpx.get(f"{url}/startupz", timeout=2.0)
            if resp.status_code == 200:
                return time.monotonic() - t0
            last = f"HTTP {resp.status_code}: {resp.text[:120]}"
        except Exception as exc:
            last = repr(exc)
        time.sleep(interval_s)
    raise TimeoutError(f"{url} not ready after {timeout_s} s (last: {last})")


class FleetMember(SupervisedReplica):
    """A supervised stub replica with the fleet controller's handle surface
    (ISSUE 6): a per-member maintenance file (the PR 2 preemption source,
    polled fast) and a pool label. `preempt()` is the storm fault — the
    member drains, exits 83, and its supervisor warm-restarts it;
    `clear_preemption()` removes the source so the restarted child doesn't
    immediately re-preempt itself (the controller calls it once it observes
    the member go down)."""

    def __init__(
        self,
        port: int,
        pidfile: str,
        preempt_file: str,
        pool: str = "spot",
        env: dict | None = None,
        **kwargs,
    ) -> None:
        self.preempt_file = preempt_file
        self.pool = pool
        member_env = {
            "SPOTTER_TPU_PREEMPTION_FILE": preempt_file,
            "SPOTTER_TPU_PREEMPTION_POLL_S": "0.05",
            "SPOTTER_TPU_POOL": pool,
        }
        if env:
            member_env.update(env)
        super().__init__(port, pidfile, env=member_env, **kwargs)

    def alive(self) -> bool:
        """The SUPERVISOR process (a dead child mid-restart still counts as
        alive — the supervisor owns bringing it back)."""
        return self.proc.poll() is None

    def preempt(self) -> None:
        tmp = f"{self.preempt_file}.tmp"
        with open(tmp, "w") as f:
            f.write("injected preemption storm")
        os.replace(tmp, self.preempt_file)  # atomic: the watcher never sees partial

    def clear_preemption(self) -> None:
        try:
            os.unlink(self.preempt_file)
        except OSError:
            pass


def rollout_spawner(workdir: str, version: str, pool: str = "on_demand",
                    env: dict | None = None, manifest: str | None = None,
                    **replica_kwargs):
    """Factory for `RolloutController`'s spawner over REAL subprocess
    members (ISSUE 15): each call spawns one supervised stub replica with
    `SPOTTER_TPU_BUILD_VERSION=<version>` in its environment, so the
    child stamps the version into its identity block and every
    `X-Spotter-Version` header — the cross-process form of the in-process
    drill members `testing/chaos_matrix.py` builds. The returned member
    carries a `version` attribute the controller reads at adoption."""
    member_env = {"SPOTTER_TPU_BUILD_VERSION": version}
    if env:
        member_env.update(env)
    base = fleet_spawner(workdir, pool, env=member_env, manifest=manifest,
                         **replica_kwargs)

    def spawn() -> FleetMember:
        member = base()
        member.version = version
        return member

    return spawn


def fleet_spawner(workdir: str, pool: str, env: dict | None = None,
                  manifest: str | None = None, **replica_kwargs):
    """Factory for `FleetController` PoolSpec.spawner: each call spawns one
    FleetMember on a fresh ephemeral port with its own pidfile + maintenance
    file under `workdir`. The member is returned immediately (HTTP binds
    before bring-up); the controller's health loop promotes it when
    /healthz goes 200. With `manifest=` every member self-registers in the
    endpoints manifest (ISSUE 16 adoption surface)."""

    def spawn() -> FleetMember:
        (port,) = pick_ports(1)
        tag = f"{pool}-{port}"
        return FleetMember(
            port,
            os.path.join(workdir, f"{tag}.pid"),
            os.path.join(workdir, f"{tag}.preempt"),
            pool=pool,
            env=env,
            manifest=manifest,
            **replica_kwargs,
        )

    return spawn


def start_replicas(
    n: int, workdir: str, **replica_kwargs
) -> list[SupervisedReplica]:
    """Spawn + wait-ready N supervised stub replicas. On any bring-up
    failure, everything spawned so far is torn down with its output in the
    raised error."""
    ports = pick_ports(n)
    replicas = [
        SupervisedReplica(
            port, os.path.join(workdir, f"replica-{port}.pid"), **replica_kwargs
        )
        for port in ports
    ]
    try:
        for r in replicas:
            wait_ready(r.url)
    except Exception:
        outputs = [r.shutdown() for r in replicas]
        raise RuntimeError(
            "replica bring-up failed:\n" + "\n---\n".join(o[-2000:] for o in outputs)
        ) from None
    return replicas
