"""Multi-host bring-up: TPU_WORKER_* env -> jax.distributed.

The reference wires pods together with env + cluster DNS (MODEL_NAME into the
serve pod — serve.py:199; head-svc DNS into the proxy — handlers.go:298-304).
The multi-host TPU workerGroup does the same: the RayService template
(configs/rayservice-tpu-template.yaml) injects TPU_WORKER_ID and
TPU_WORKER_HOSTNAMES, and this module turns them into a
`jax.distributed.initialize` call so all hosts join one XLA runtime; cross-
host collectives then ride DCN while intra-slice traffic stays on ICI
(SURVEY.md §2.4).
"""

import logging
import os

import jax

logger = logging.getLogger(__name__)

_COORD_PORT_DEFAULT = 8476

# Coordinator-join timeout (ISSUE 2): jax.distributed.initialize's default is
# 300 s of silent blocking; a preempted coordinator host would hang every
# other worker's bring-up for 5 minutes before any error surfaces — longer
# than the whole restart budget on spot capacity. 120 s still covers a slow
# pod schedule while failing fast enough for the supervisor to retry.
COORD_TIMEOUT_ENV = "SPOTTER_TPU_COORD_TIMEOUT_S"
DEFAULT_COORD_TIMEOUT_S = 120


def coordinator_timeout_s() -> int:
    raw = os.environ.get(COORD_TIMEOUT_ENV, "").strip()
    if not raw:
        return DEFAULT_COORD_TIMEOUT_S
    try:
        timeout = int(float(raw))
    except ValueError:
        raise ValueError(
            f"{COORD_TIMEOUT_ENV} must be a number of seconds, got {raw!r}"
        ) from None
    if timeout <= 0:
        raise ValueError(f"{COORD_TIMEOUT_ENV} must be > 0, got {raw!r}")
    return timeout


def multihost_env_summary() -> dict:
    """The env contract the k8s template must satisfy (also used by tests)."""
    return {
        "TPU_WORKER_ID": os.environ.get("TPU_WORKER_ID"),
        "TPU_WORKER_HOSTNAMES": os.environ.get("TPU_WORKER_HOSTNAMES"),
        "SPOTTER_COORDINATOR_PORT": os.environ.get(
            "SPOTTER_COORDINATOR_PORT", str(_COORD_PORT_DEFAULT)
        ),
        "SPOTTER_TPU_COORD_TIMEOUT_S": str(coordinator_timeout_s()),
    }


def _enable_cpu_collectives() -> None:
    """On the CPU backend, multi-process computations need a CPU collectives
    implementation: the default "none" fails any cross-process jit with
    "Multiprocess computations aren't implemented on the CPU backend". The
    2-process CPU dryruns — the stand-in for a DCN slice
    (tests/test_multihost.py) — hit exactly that, so arm gloo before
    distributed init when we're on CPU. Must run before the backend
    initializes; a no-op on TPU."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize_multihost(force: bool = False) -> bool:
    """Join the jax.distributed cluster if the TPU_WORKER_* env says we're in one.

    Returns True when distributed init ran (or had already run), False for the
    single-host case. Safe to call unconditionally at serving bootstrap — the
    single-host path is a no-op, mirroring how the reference's serve.py runs
    identically in 1-pod and autoscaled deployments. A TPU host may export
    both variables for a single worker: one hostname is one host, and one
    host never joins jax.distributed whatever the variables say.
    """
    env = multihost_env_summary()
    hostnames = env["TPU_WORKER_HOSTNAMES"] or ""
    worker_id = env["TPU_WORKER_ID"]
    hosts = [h.strip() for h in hostnames.split(",") if h.strip()]
    if not hosts or worker_id is None:
        if force:
            raise RuntimeError(
                "initialize_multihost(force=True) but TPU_WORKER_HOSTNAMES / "
                "TPU_WORKER_ID are not set"
            )
        return False
    if len(hosts) == 1:
        return False

    coordinator = f"{hosts[0]}:{env['SPOTTER_COORDINATOR_PORT']}"
    if jax.distributed.is_initialized():  # already up
        return True
    _enable_cpu_collectives()
    timeout_s = coordinator_timeout_s()
    logger.info(
        "multihost init: coordinator=%s num_processes=%d process_id=%s "
        "timeout=%ds",
        coordinator, len(hosts), worker_id, timeout_s,
    )
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=len(hosts),
            process_id=int(worker_id),
            initialization_timeout=timeout_s,
        )
    except Exception as exc:
        # A dead/preempted coordinator must read as a bounded, actionable
        # failure (the supervisor's restart-with-backoff handles it), not a
        # bring-up that silently never returns.
        raise RuntimeError(
            f"multihost bring-up failed (coordinator {coordinator}, "
            f"join timeout {timeout_s} s — set {COORD_TIMEOUT_ENV} to adjust; "
            f"a preempted coordinator host fails here instead of hanging): "
            f"{exc}"
        ) from exc
    return True
