"""Process exit-code taxonomy: clean / fatal / retryable-infra.

A multi-host job that just "exits 1" tells the orchestrator nothing: a
deterministic divergence (retry = burn the same TPU hours again) and a
flaky rendezvous (retry = the run completes) look identical. Following the
sysexits EX_TEMPFAIL convention, failures here are classified into three
documented classes the k8s layer consumes (``k8s/entrypoint.sh`` logs the
class; ``k8s/job.yaml``'s ``podFailurePolicy`` fails the Job fast on fatal
codes and lets retryable ones burn the backoff budget):

==== ======================= ==============================================
code class                   meaning
==== ======================= ==============================================
0    clean                   run completed (incl. preemption save + exit)
1    fatal (training)        deterministic failure — divergence, bad data,
                             bug; retrying reproduces it
2    fatal (config)          invalid config/CLI usage, or an incompatible
                             resume topology change (elastic.py); retrying
                             is useless
75   retryable infra         EX_TEMPFAIL — transient environment failure
                             (rendezvous, dataset fetch, storage blip);
                             the orchestrator should restart the pod
76   retryable hang          the hang watchdog hard-exited a stalled run
                             (stuck collective / wedged host); restart
==== ======================= ==============================================

This module is deliberately dependency-free (no jax, no pydantic) so the
CLI and k8s tooling can import it without dragging in the runtime.
"""

from __future__ import annotations

EXIT_OK = 0
EXIT_TRAIN_FAILURE = 1
EXIT_CONFIG_ERROR = 2
# sysexits.h EX_TEMPFAIL: "temporary failure, user is invited to retry".
EXIT_RETRYABLE_INFRA = 75
# Dedicated code for watchdog-detected stalls, distinct from generic infra
# failures so a fleet can count hangs separately; still retryable.
EXIT_HANG_DETECTED = 76

RETRYABLE_EXIT_CODES = frozenset({EXIT_RETRYABLE_INFRA, EXIT_HANG_DETECTED})
FATAL_EXIT_CODES = frozenset({EXIT_TRAIN_FAILURE, EXIT_CONFIG_ERROR})


def is_retryable(code: int) -> bool:
    """True when the orchestrator should restart the pod for this code."""
    return code in RETRYABLE_EXIT_CODES


class RetryableInfraError(RuntimeError):
    """Raise (or wrap a cause with) this to mark a failure as transient
    infrastructure trouble: the CLI maps it to :data:`EXIT_RETRYABLE_INFRA`
    so the orchestrator restarts the pod instead of failing the Job."""


# Exception types that are transient by nature even when nobody wrapped
# them: network/storage hiccups and timeouts. OSError at large is NOT here
# — a missing file or permission error is deterministic.
_RETRYABLE_TYPES: tuple[type[BaseException], ...] = (
    RetryableInfraError,
    TimeoutError,
    ConnectionError,
    InterruptedError,
)


def _exception_chain(exc: BaseException):
    """``exc`` and its cause/context chain, cycle-safe.

    Mirrors traceback display rules: explicit ``__cause__`` always counts;
    implicit ``__context__`` only when not suppressed — ``raise X from
    None`` deliberately severs the chain, so a deterministic error raised
    while HANDLING a transient one must not inherit "retryable" from the
    exception its author disowned.
    """
    seen: set[int] = set()
    node: BaseException | None = exc
    while node is not None and id(node) not in seen:
        seen.add(id(node))
        yield node
        nxt = node.__cause__
        if nxt is None and not node.__suppress_context__:
            nxt = node.__context__
        node = nxt


def exit_code_for_exception(exc: BaseException) -> int:
    """Map an exception escaping a CLI handler onto the taxonomy.

    Walks the cause/context chain so a retryable root cause wrapped by a
    generic layer (``RuntimeError(...) from TimeoutError``) still
    classifies as retryable. Deterministic training failures (divergence,
    exhausted rollback budget) are explicitly fatal: retrying replays the
    same math. Unknown exceptions default to fatal — claiming "retryable"
    for a genuine bug would loop the orchestrator forever.
    """
    # Local imports: keep this module importable without jax/pydantic.
    from ..autotune.plan import MeshPlanError
    from ..distributed import PlatformError
    from .elastic import TopologyMismatchError
    from .faults import InjectedFault
    from .guard import NonFiniteLossError
    from .spike import RollbackBudgetExceededError

    for node in _exception_chain(exc):
        # An incompatible topology change is a CONFIG problem: the same
        # config replays the same mismatch, so the orchestrator must not
        # burn restarts on it. An infeasible mesh plan (axis sizes vs
        # device count / capability rules, autotune/plan.py) is the same
        # class: deterministic from config, restarting cannot help.
        # So is run.device naming a platform JAX did not select (no chip
        # on this machine): the same pod on the same node replays it.
        if isinstance(node, (TopologyMismatchError, MeshPlanError, PlatformError)):
            return EXIT_CONFIG_ERROR
    for node in _exception_chain(exc):
        # Deterministic divergence beats any wrapped transient error.
        if isinstance(node, (NonFiniteLossError, RollbackBudgetExceededError)):
            return EXIT_TRAIN_FAILURE
    for node in _exception_chain(exc):
        # InjectedFault simulates flaky infra (dataset load, rendezvous) —
        # classifying it retryable lets tests drive the taxonomy end to end.
        if isinstance(node, _RETRYABLE_TYPES) or isinstance(node, InjectedFault):
            return EXIT_RETRYABLE_INFRA
    return EXIT_TRAIN_FAILURE


__all__ = [
    "EXIT_OK",
    "EXIT_TRAIN_FAILURE",
    "EXIT_CONFIG_ERROR",
    "EXIT_RETRYABLE_INFRA",
    "EXIT_HANG_DETECTED",
    "RETRYABLE_EXIT_CODES",
    "FATAL_EXIT_CODES",
    "RetryableInfraError",
    "exit_code_for_exception",
    "is_retryable",
]
