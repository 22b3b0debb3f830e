"""Test harness config.

Multi-device semantics without hardware (SURVEY.md section 4 implication):
force the JAX CPU backend with 8 virtual devices -- the ``local[*]`` analogue
of the reference's Spark test fixtures. Must run before jax is imported.
Children that tests start inherit all three settings through the environment.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the suite neither reads nor fills the persistent compile cache that
# utils.platform.ensure_backend places (<checkout>/.jax_cache): the tests of
# the cache itself say where it would go, in children of their own
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest


def _lockwatch_enabled() -> bool:
    return os.environ.get("PIO_LOCKWATCH", "1") != "0"


@pytest.fixture(scope="session", autouse=True)
def _lockwatch_session():
    """Runtime validation of the static C001 rule (``pio check``): every
    predictionio_tpu-constructed lock is watched for the whole suite, so an
    acquisition-order inversion anywhere in tier-1 surfaces as a test
    failure even when the timing never actually deadlocks.
    ``PIO_LOCKWATCH=0`` opts out."""
    if not _lockwatch_enabled():
        yield
        return
    from predictionio_tpu.analysis import lockwatch

    lockwatch.install()
    yield
    lockwatch.uninstall()


@pytest.fixture(autouse=True)
def _lockwatch_inversions(_lockwatch_session):
    """Fail the test during which a lock-order inversion was first
    observed (background threads charge their inversions to whichever
    test is running -- close enough to localize the bug)."""
    if not _lockwatch_enabled():
        yield
        return
    from predictionio_tpu.analysis import lockwatch

    watch = lockwatch.global_watch()
    before = len(watch.inversions)
    yield
    fresh = watch.inversions[before:]
    assert not fresh, "lock-order inversion(s) observed: " + "; ".join(
        inv.detail for inv in fresh
    )


def _leakwatch_enabled() -> bool:
    from predictionio_tpu.analysis import leakwatch

    return leakwatch.enabled_default()


@pytest.fixture(scope="session", autouse=True)
def _leakwatch_session():
    """Runtime validation of the static R001/R002 rules (``pio check``):
    every Span and every predictionio_tpu-constructed Semaphore is
    watched for the whole suite, so a span left unfinished or a permit
    held past a test's end surfaces as a test failure.
    ``PIO_LEAKWATCH=0`` opts out."""
    if not _leakwatch_enabled():
        yield
        return
    from predictionio_tpu.analysis import leakwatch

    leakwatch.install()
    yield
    leakwatch.uninstall()


@pytest.fixture(autouse=True)
def _leakwatch_leaks(_leakwatch_session):
    """Fail the test during which a span leaked or a permit went
    unbalanced (after a short settle window: teardown may finish a
    straggler span a few milliseconds after the test body returns)."""
    if not _leakwatch_enabled():
        yield
        return
    from predictionio_tpu.analysis import leakwatch

    watch = leakwatch.global_watch()
    spans_before = watch.span_snapshot()
    debts_before = watch.permit_debts()
    yield
    leaked = leakwatch.settle(
        lambda: watch.new_pending_spans(spans_before)
    )
    assert not leaked, "unfinished span(s) leaked by this test: " + ", ".join(
        f"{s.op} (trace {s.trace_id})" for s in leaked
    )
    debts = leakwatch.settle(
        lambda: leakwatch.LeakWatch.new_debts(
            debts_before, watch.permit_debts()
        )
    )
    assert not debts, (
        "admission permit(s) held past the test's end: "
        + ", ".join(f"{site}: +{n}" for site, n in sorted(debts.items()))
    )


@pytest.fixture()
def storage_env(tmp_path, monkeypatch):
    """Point the storage registry at a fresh sqlite file per test."""
    from predictionio_tpu.data import storage as storage_registry

    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    storage_registry.reset()
    yield storage_registry
    storage_registry.reset()


@pytest.fixture
def a_small_als_budget(monkeypatch):
    """``parallel.als.EINSUM_GATHER_BUDGET_BYTES`` at 64 KiB: every ALS block
    of a test's size is worked in row chunks. The rule is asked as a program
    is traced, so built programs are forgotten on the way in and out."""
    from predictionio_tpu.parallel import als

    def forget():
        als._build_iteration.cache_clear()
        als._build_stream_programs.cache_clear()

    forget()
    monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", 1 << 16)
    yield
    forget()


@pytest.fixture(params=["whole", "chunked"])
def worked(request):
    """ALS blocks worked whole (the budget as it is) or in row chunks
    (``a_small_als_budget``); a test may name one with
    ``parametrize("worked", [...], indirect=True)``."""
    if request.param == "chunked":
        request.getfixturevalue("a_small_als_budget")
    return request.param
