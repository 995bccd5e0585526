"""Test bootstrap: force an 8-virtual-device CPU platform before JAX imports.

This is the TPU-build analogue of the reference's mocked-collective technique
(reference tests/test_distributed.py:609-619): instead of faking
``all_gather``/``all_reduce``, we give XLA eight real host devices so mesh
shardings and collectives execute for real in a single process.
"""

import os
import sys

# Make the in-repo package importable without an editable install, both here
# and in every subprocess the tests spawn (CLI and multi-process tests run
# ``python -m llmtrain_tpu`` from temp dirs).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)
os.environ["PYTHONPATH"] = (
    _REPO_ROOT + os.pathsep + os.environ["PYTHONPATH"]
    if os.environ.get("PYTHONPATH")
    else _REPO_ROOT
)

# Unit tests are hermetic: JAX is held to the CPU with eight virtual
# devices, whatever the machine has. Escape hatch: LLMTRAIN_TEST_TPU=1 keeps
# the real accelerator so the on-chip compiled-kernel suite
# (tests/test_tpu_compiled.py) can run on the machine with the chip.
_use_tpu = os.environ.get("LLMTRAIN_TEST_TPU") == "1"
if not _use_tpu:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

# XLA's CPU client sizes its thread pools from the schedulable cores. With
# eight virtual devices on an eight-core box a collective needs every pool
# thread at once, so any overlapping async work (the next step's dispatch, a
# checkpoint's D2H, the prefetcher's H2D) can starve one participant until
# XLA's 40 s rendezvous timeout aborts the process ("Termination timeout
# for `all gather ...` exceeded") — and a crashed xdist worker then hangs
# the loadfile scheduler until the run's clock is out. NPROC is XLA's own
# pool-size override (xla/pjrt/utils.cc DefaultThreadPoolSize); inherited
# by every subprocess the tests spawn.
os.environ.setdefault("NPROC", "32")

# Persistent compilation cache for the suite: the gate is dominated by jit
# compiles of shapes that never change between runs. Same rule as the
# program (llmtrain_tpu.distributed.configure_compilation_cache): where
# JAX_COMPILATION_CACHE_DIR is set it stands and nothing here touches it;
# otherwise the suite's own fixed in-checkout directory is exported BEFORE
# jax is imported, so jax reads it itself and every subprocess the tests
# spawn (CLI / multi-process) inherits the same cache.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_REPO_ROOT, ".cache", "jax-tests")
)

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

from llmtrain_tpu.distributed import configure_compilation_cache  # noqa: E402

configure_compilation_cache()

# Start-up recording (telemetry/timeline.py's process buffer, its listeners
# and stall watch) is off for the suite: every EventTimeline a test builds
# would otherwise adopt the compile spans of the tests before it. The tests
# of the buffer switch it on for themselves (tests/test_startup_spans.py).
from llmtrain_tpu.telemetry.timeline import process_recording  # noqa: E402

process_recording(False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def restore_llmtrain_logger():
    """In-process cli.main and configure_logging reconfigure the llmtrain
    logger (propagate off, handlers re-targeted) — restore it after every
    test, or every later caplog-based test in the same worker goes blind;
    which file that is depends on how xdist deals the files out."""
    import logging

    logger = logging.getLogger("llmtrain")
    saved = (logger.propagate, logger.level, list(logger.handlers))
    yield
    for handler in list(logger.handlers):
        if handler not in saved[2]:
            if isinstance(handler, logging.FileHandler):
                handler.close()
            logger.removeHandler(handler)
    for handler in saved[2]:
        if handler not in logger.handlers:
            logger.addHandler(handler)
    logger.propagate = saved[0]
    logger.setLevel(saved[1])


def pytest_collection_modifyitems(config, items):
    """Under LLMTRAIN_TEST_TPU=1 run ONLY the TPU-gated compiled tests.

    Everything else assumes the hermetic 8-virtual-device CPU mesh this
    flag disables, so running it against the real backend would fail (or
    pass against the wrong topology)."""
    if not _use_tpu:
        return
    # Fail loudly rather than silently skipping everything: an all-skipped
    # run exits 0 and would record the compiled-kernel suite as green when
    # nothing executed.
    try:
        backend = jax.default_backend()
    except Exception as exc:  # backend init failure
        raise pytest.UsageError(
            f"LLMTRAIN_TEST_TPU=1 but the TPU backend failed to initialize: {exc}"
        ) from exc
    if backend != "tpu":
        raise pytest.UsageError(
            f"LLMTRAIN_TEST_TPU=1 but jax.default_backend() is {backend!r}, not 'tpu'"
        )
    skip = pytest.mark.skip(
        reason="LLMTRAIN_TEST_TPU=1 runs only tests/test_tpu_compiled.py"
    )
    for item in items:
        if "test_tpu_compiled" not in str(item.fspath):
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _reset_distributed_state():
    """Guarantee distributed-state teardown between tests.

    Analogue of the reference's autouse teardown fixture
    (reference tests/test_distributed.py:31-35).
    """
    yield
    from llmtrain_tpu.distributed import teardown_distributed

    teardown_distributed()
