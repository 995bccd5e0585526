"""Multi-process runtime + named device mesh.

Parity target: reference ``src/llmtrain/distributed/__init__.py`` (DDPState,
setup_ddp, teardown_ddp) re-imagined for JAX:

* ``DistState`` mirrors ``DDPState`` (frozen, ``is_main == (rank == 0)``
  invariant enforced in ``__post_init__``, reference :28-31).
* ``setup_distributed`` mirrors ``setup_ddp``'s contract — idempotent with a
  warning (reference :75-93), env-var-first resolution with config fallback
  (reference :100-118) — but rendezvous is ``jax.distributed.initialize``
  (coordinator over DCN) instead of a gloo process group. The same env names
  (RANK/WORLD_SIZE/MASTER_ADDR/MASTER_PORT) are honoured so the K8s
  IndexedJob bootstrap carries over unchanged; JAX-native names
  (JAX_PROCESS_ID/JAX_NUM_PROCESSES/JAX_COORDINATOR_ADDRESS) win over them.
* There is no DDP wrapper to build: gradient sync is a sharding property of
  the jit-compiled train step (see ``llmtrain_tpu/parallel``), with XLA
  emitting psum/reduce-scatter over ICI.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax

from ..config.schemas import DistributedConfig, MeshConfig
from ..utils.logging import get_logger

_DEFAULT_COORDINATOR_PORT = 29500

# Module-level idempotency latch (the analogue of torch's
# dist.is_initialized() check, reference distributed/__init__.py:75).
_ACTIVE_STATE: "DistState | None" = None
_JAX_DIST_INITIALIZED = False


@dataclass(frozen=True)
class DistState:
    """Resolved multi-process topology for this process.

    ``process_index``/``num_processes`` are the JAX names for the reference's
    rank/world_size; ``is_main`` gates all filesystem and tracker I/O.
    """

    process_index: int
    num_processes: int
    local_device_count: int
    is_main: bool
    coordinator: str | None = None

    def __post_init__(self) -> None:
        if self.num_processes < 1:
            raise ValueError("num_processes must be >= 1")
        if not (0 <= self.process_index < self.num_processes):
            raise ValueError("process_index must be in [0, num_processes)")
        if self.is_main != (self.process_index == 0):
            raise ValueError("is_main must equal (process_index == 0)")

    # Reference-compatible aliases (DDPState.rank / .world_size).
    @property
    def rank(self) -> int:
        return self.process_index

    @property
    def world_size(self) -> int:
        return self.num_processes


def _env_int(*names: str) -> int | None:
    for name in names:
        raw = os.environ.get(name)
        if raw is not None and raw != "":
            try:
                return int(raw)
            except ValueError as exc:
                raise ValueError(f"Environment variable {name}={raw!r} is not an integer") from exc
    return None


def _resolve_int(env_names: tuple[str, ...], config_value: int | None, default: int) -> int:
    env_val = _env_int(*env_names)
    if env_val is not None:
        return env_val
    if config_value is not None:
        return config_value
    return default


def resolve_topology(cfg: DistributedConfig) -> tuple[int, int, str | None]:
    """Resolve (process_id, num_processes, coordinator) env-first.

    JAX-native env names beat torch-compat names beat config values beat
    defaults — mirroring reference distributed/__init__.py:100-118.
    """
    num_processes = _resolve_int(("JAX_NUM_PROCESSES", "WORLD_SIZE"), cfg.num_processes, 1)
    explicit_process_id = _env_int("JAX_PROCESS_ID", "RANK")
    if explicit_process_id is None:
        explicit_process_id = cfg.process_id
    if explicit_process_id is None and num_processes > 1:
        # Fail fast with a diagnosable error instead of letting every process
        # claim rank 0 and hang in rendezvous until the timeout.
        raise ValueError(
            "Multi-process run (num_processes "
            f"= {num_processes}) but process id is unset; set RANK/JAX_PROCESS_ID "
            "or distributed.process_id"
        )
    process_id = explicit_process_id if explicit_process_id is not None else 0

    # "" counts as unset for the address, matching _env_int's empty-as-unset rule.
    coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS") or None
    if coordinator is None:
        addr = os.environ.get("MASTER_ADDR") or cfg.coordinator_addr
        port = _resolve_int(("MASTER_PORT",), cfg.coordinator_port, _DEFAULT_COORDINATOR_PORT)
        coordinator = f"{addr}:{port}" if addr else None
    return process_id, num_processes, coordinator


class PlatformError(ValueError):
    """``run.device`` and the platform JAX actually selected disagree.

    Deterministic from config + host (restarting replays it), so the CLI
    maps it to the config exit code (resilience/exit_codes.py)."""


def configure_platform(device: str) -> None:
    """The platform rule, stated once: ``run.device`` NAMES the platform
    the run executes on — it is never a preference with a fallback.

    ``cpu`` pins JAX to the CPU backend here, BEFORE backend init, so a
    host that holds a chip leaves it alone; ``tpu`` leaves JAX's own
    selection in place. ``resolve_devices`` enforces both directions once
    the backend is up (it cannot run here: touching the backend before
    ``jax.distributed.initialize`` breaks multi-process rendezvous).
    """
    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")


def resolve_devices(device: str) -> list:
    """The global device list for ``run.device``, or :class:`PlatformError`
    when JAX selected another platform: ``tpu`` on a machine without a
    chip must not carry on on the CPU under the name ``tpu``, and ``cpu``
    whose pin came too late must not run on the chip unannounced."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != device:
        hint = (
            "no TPU is attached to this process (is another process holding "
            "the chip, or is JAX_PLATFORMS pinned to cpu?)"
            if device == "tpu"
            else "the backend was initialized before run.device could pin it"
        )
        raise PlatformError(
            f"run.device is {device!r} but JAX selected platform "
            f"{platform!r} ({devices[0].device_kind}): {hint}"
        )
    return devices


_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# Fixed, in-checkout (``.cache/`` is git-ignored): the path is part of the
# cache key, so a directory that moves between runs never hits.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(_REPO_ROOT, ".cache", "jax")


def resolve_compilation_cache_dir(config_dir: str | None = None) -> str:
    """The directory JAX's persistent compilation cache lives in. Single
    owner of the rule (chip_smoke.py's cache telemetry reads it).

    ``JAX_COMPILATION_CACHE_DIR`` (JAX's own variable) places the cache
    from outside and nothing in this program overrides it; otherwise
    ``run.compilation_cache_dir`` (``config_dir``), otherwise the fixed
    in-checkout default. ``JAX_ENABLE_COMPILATION_CACHE=false`` is JAX's
    own off switch.
    """
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or config_dir
        or DEFAULT_COMPILATION_CACHE_DIR
    )


def compilation_cache_entries(config_dir: str | None = None) -> int:
    """Entry count of the persistent compilation cache (0 = no dir yet):
    the before/after evidence chip_smoke.py prints."""
    try:
        return len(os.listdir(resolve_compilation_cache_dir(config_dir)))
    except OSError:
        return 0


def configure_compilation_cache(config_dir: str | None = None) -> None:
    """Enable JAX's persistent compilation cache (new capability; the
    reference has no compiled artifacts to cache) so restarts, sweep
    candidates and podFailurePolicy-restarted k8s Jobs pay each compile
    once. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read
    it and this sets NO directory in code. Safe to call multiple times.

    Every entry point passes here before its first compile, so this is
    also where start-up recording begins (``telemetry/timeline.py``:
    ``startup/import`` closes, JAX's compile and cache events become
    spans, the stall watch runs)."""
    from ..telemetry.timeline import watch_startup

    watch_startup()
    # Cache everything that took noticeable compile time; tiny programs
    # aren't worth the disk round-trip.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = os.path.abspath(resolve_compilation_cache_dir(config_dir))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)


def _tpu_autodetect_available(cfg: DistributedConfig) -> bool:
    """True when a MULTI-host TPU pod-slice env can drive a bare
    ``initialize()`` and no explicit topology was given (explicit env/config
    always wins). Single-host slices need no distributed init at all."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) < 2:
        return False
    explicit = (
        _env_int("JAX_NUM_PROCESSES", "WORLD_SIZE") is not None
        or cfg.num_processes is not None
    )
    return not explicit


def setup_distributed(cfg: DistributedConfig) -> DistState:
    """Initialize the JAX distributed runtime (idempotent).

    With one process this is a no-op beyond resolving the topology. With
    several, all processes block in ``jax.distributed.initialize`` until the
    coordinator has heard from everyone — the process-group boundary the
    reference hits in ``dist.init_process_group`` (reference :130-136).
    """
    global _ACTIVE_STATE, _JAX_DIST_INITIALIZED
    logger = get_logger()

    if _ACTIVE_STATE is not None:
        logger.warning("distributed runtime already initialized; returning existing state")
        return _ACTIVE_STATE

    if _tpu_autodetect_available(cfg):
        # GKE TPU pod slice: the TPU runtime env (TPU_WORKER_ID /
        # TPU_WORKER_HOSTNAMES, injected by the GKE webhook) lets JAX derive
        # coordinator + process ids itself — no explicit topology needed.
        jax.distributed.initialize()
        _JAX_DIST_INITIALIZED = True
        state = DistState(
            process_index=jax.process_index(),
            num_processes=jax.process_count(),
            local_device_count=jax.local_device_count(),
            is_main=jax.process_index() == 0,
            coordinator=None,
        )
        _ACTIVE_STATE = state
        logger.info(
            "distributed runtime auto-initialized from TPU environment: "
            "process %d/%d, %d local device(s)",
            state.process_index,
            state.num_processes,
            state.local_device_count,
        )
        return state

    process_id, num_processes, coordinator = resolve_topology(cfg)

    if num_processes > 1:
        if coordinator is None:
            raise ValueError(
                "Multi-process run requires a coordinator address "
                "(set MASTER_ADDR/MASTER_PORT, JAX_COORDINATOR_ADDRESS, "
                "or distributed.coordinator_addr/coordinator_port)"
            )
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=None,
                initialization_timeout=cfg.timeout_sec,
                # The shutdown barrier must tolerate the same straggler
                # skew as startup: on oversubscribed hosts (N procs per
                # core in CI) ranks can reach teardown minutes apart, and
                # jax's 300 s default then kills otherwise-green runs at
                # the very end.
                shutdown_timeout_seconds=max(300, cfg.timeout_sec),
            )
        except Exception:
            # A failed connect (coordinator not up yet — the case the CLI's
            # backoff retry exists for) leaves jax's global distributed
            # state partially set; without a teardown every later attempt
            # dies on "initialize should only be called once" instead of
            # actually retrying the rendezvous. shutdown() resets
            # client/service to None, making initialize callable again.
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 — best-effort cleanup
                pass
            raise
        _JAX_DIST_INITIALIZED = True
        process_id = jax.process_index()
        num_processes = jax.process_count()

    state = DistState(
        process_index=process_id,
        num_processes=num_processes,
        local_device_count=jax.local_device_count(),
        is_main=process_id == 0,
        coordinator=coordinator,
    )
    _ACTIVE_STATE = state
    logger.info(
        "distributed runtime ready: process %d/%d, %d local device(s)",
        state.process_index,
        state.num_processes,
        state.local_device_count,
    )
    return state


def teardown_distributed() -> None:
    """Shut down the distributed runtime if this process started it."""
    global _ACTIVE_STATE, _JAX_DIST_INITIALIZED
    if _JAX_DIST_INITIALIZED:
        jax.distributed.shutdown()
        _JAX_DIST_INITIALIZED = False
    _ACTIVE_STATE = None


def active_state() -> DistState | None:
    return _ACTIVE_STATE


def allgather_any(flag: bool) -> bool:
    """Cross-process OR of a local boolean (collective: EVERY process must
    call this at the same point). Single-process: identity. The consensus
    primitive for "did ANY rank see it" decisions — preemption stop,
    loss-spike rollback — where acting on a local-only flag would desync
    the ranks into a deadlocked collective."""
    if jax.process_count() == 1:
        return bool(flag)
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.asarray([bool(flag)]))
    return bool(np.asarray(gathered).any())


def allgather_scalar(value: float) -> "list[float]":
    """Per-process list of a local scalar, indexed by process id
    (collective). Single-process: one-element list. Feeds the straggler
    telemetry's per-host step times."""
    import numpy as np

    if jax.process_count() == 1:
        return [float(value)]
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.asarray([float(value)]))
    return [float(x) for x in np.asarray(gathered).reshape(-1)]


def broadcast_int_from_main(value: int) -> int:
    """Every process returns process 0's value (collective). Single-process:
    identity. Used where rank 0 owns the decision (e.g. which checkpoint
    step a rollback restores) and the others must follow it exactly."""
    if jax.process_count() == 1:
        return int(value)
    import numpy as np
    from jax.experimental import multihost_utils

    agreed = multihost_utils.broadcast_one_to_all(np.int64(value))
    return int(np.asarray(agreed))


MESH_AXES = ("data", "fsdp", "tensor", "sequence", "pipeline", "expert")


def resolve_mesh_axes(mesh_cfg: MeshConfig, device_count: int) -> dict[str, int]:
    """Materialize axis sizes, expanding a single ``-1`` wildcard.

    The math lives in the mesh planner (autotune/plan.py) — one owner for
    wildcard/divisibility resolution across trainer, fleet, and tuner.
    Failures raise ``MeshPlanError`` (a ValueError subclass mapped to the
    config exit code 2) instead of surfacing as an opaque pjit error.
    """
    from ..autotune.plan import resolve_axis_sizes

    return resolve_axis_sizes(mesh_cfg.axis_sizes(), device_count)


def build_mesh(mesh_cfg: MeshConfig | None = None, devices=None) -> jax.sharding.Mesh:
    """Build the global named device mesh.

    Axis order puts ``data`` outermost (slowest-varying) so data-parallel
    replicas span hosts/DCN while tensor/sequence shards stay within a host's
    ICI neighbourhood — the layout recommended by the scaling playbook.
    """
    from jax.experimental import mesh_utils

    if mesh_cfg is None:
        mesh_cfg = MeshConfig()
    if devices is None:
        devices = jax.devices()
    sizes = resolve_mesh_axes(mesh_cfg, len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return jax.sharding.Mesh(device_array, MESH_AXES)
