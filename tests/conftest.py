"""Test configuration: run the suite on an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing distributed logic with local
processes + gloo (SURVEY.md §4): here a single process with 8 XLA host devices
stands in for an 8-chip TPU slice. chip_smoke.py and benchmark/run.py use the
real chip.
"""
import os
import threading

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# -- lock-order sanitizer (analysis/lock_order.py, ISSUE 7) -----------------
# Installed BEFORE anything imports paddle_tpu so module-level framework
# locks are created through the patched constructors and get witnessed.
# The module is loaded by file path (pure stdlib, no jax) and pre-registered
# under its canonical name so later `import paddle_tpu.analysis.lock_order`
# yields this same instance (and this same edge graph).
_LOCK_ORDER = None
if os.environ.get("FLAGS_lock_order_check", "").lower() in ("1", "true", "yes"):
    import importlib.util
    import sys as _sys

    _lo_path = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "paddle_tpu", "analysis",
        "lock_order.py"))
    _spec = importlib.util.spec_from_file_location(
        "paddle_tpu.analysis.lock_order", _lo_path)
    _LOCK_ORDER = importlib.util.module_from_spec(_spec)
    _sys.modules["paddle_tpu.analysis.lock_order"] = _LOCK_ORDER
    _spec.loader.exec_module(_LOCK_ORDER)
    _LOCK_ORDER.install()

# thread names alive before any test ran — the leak check's baseline
_THREADS_AT_START = {t.name for t in threading.enumerate()}

import jax

jax.config.update("jax_platforms", "cpu")
# fp32 matmuls on CPU for tight numeric comparisons against NumPy
jax.config.update("jax_default_matmul_precision", "highest")

# -- host-sync sanitizer (analysis/host_sync.py, ISSUE 11) ------------------
# Patches the device→host sync points (np.asarray on jax arrays,
# jax.block_until_ready, jax.device_get) to record blocking syncs that
# happen inside train-step spans. Needs jax importable, so it installs
# AFTER the jax import (unlike the lock witness, nothing module-level
# needs catching — the patch points are module attributes).
_HOST_SYNC = None
if os.environ.get("FLAGS_host_sync_check", "").lower() in ("1", "true", "yes"):
    from paddle_tpu.analysis import host_sync as _HOST_SYNC

    _HOST_SYNC.install()

import pytest  # noqa: E402

# single-container CI: no second process to join a coordination service
_HAS_CPU_MULTIPROCESS = os.environ.get(
    "PADDLE_TPU_MULTIPROC", "").lower() in ("1", "true", "yes")


def pytest_collection_modifyitems(config, items):
    if _HAS_CPU_MULTIPROCESS:
        return
    skip = pytest.mark.skip(
        reason="multi-process jax.distributed unavailable here (set "
               "PADDLE_TPU_MULTIPROC=1 on a host that can bind a "
               "coordination service); pre-existing capability gap, not a "
               "regression")
    for item in items:
        if "requires_cpu_multiprocess" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def fresh_mesh():
    """Run the test with NO ambient mesh; restore the prior mesh after.
    Shared by the mesh-touching test files (request via an autouse
    wrapper) so the save/restore logic exists once."""
    from paddle_tpu.distributed import mesh as mesh_mod

    prev = mesh_mod.get_mesh()
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(prev)


@pytest.fixture(autouse=True, scope="module")
def _no_mesh_leaks_to_the_next_file():
    """A mesh one file leaves behind fails whichever eager file the xdist
    worker runs next (`Cannot convert GSPMDSharding {maximal device=0}`),
    and which file that is changes with the files' order: every file hands
    on the mesh it found."""
    from paddle_tpu.distributed import mesh as mesh_mod

    prev = mesh_mod.get_mesh()
    yield
    mesh_mod.set_mesh(prev)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Surface skipped AOT regression gates at suite end (VERDICT r4 #9:
    libtpu-lock contention must not silently disable test_tpu_aot)."""
    aot = [r for r in terminalreporter.stats.get("skipped", [])
           if "test_tpu_aot" in str(getattr(r, "nodeid", ""))]
    if aot:
        terminalreporter.write_sep(
            "-", f"WARNING: {len(aot)} TPU AOT gate(s) SKIPPED "
                 "(compiler unavailable after retries)")
        for r in aot:
            terminalreporter.write_line(f"  skipped: {r.nodeid}")

    # -- post-suite sanitizers (ISSUE 7) ------------------------------------
    # thread-leak check: non-daemon threads outliving the suite hang the
    # interpreter at exit; framework threads declare daemon=True (rule C001)
    # precisely so this stays empty.
    try:
        from paddle_tpu.analysis import lock_order as _lo
    except Exception:
        _lo = _LOCK_ORDER
    if _lo is not None:
        leaks = _lo.thread_leak_report(_THREADS_AT_START)
        if leaks:
            terminalreporter.write_sep(
                "-", f"WARNING: {len(leaks)} non-daemon thread(s) leaked "
                     "past the suite")
            for leak in leaks:
                terminalreporter.write_line(f"  leaked: {leak['name']}")

    # host-sync sanitizer report (only when FLAGS_host_sync_check ran)
    if _HOST_SYNC is not None:
        hs = _HOST_SYNC.report()
        if hs["in_step_syncs"]:
            terminalreporter.write_sep(
                "-", f"WARNING: host-sync sanitizer recorded "
                     f"{hs['in_step_syncs']} blocking sync(s) inside "
                     "train-step spans")
            for site in hs["sites"]:
                terminalreporter.write_line(f"  in-step sync: {site}")
        else:
            terminalreporter.write_line(
                f"host-sync sanitizer: 0 blocking syncs inside "
                f"{hs['step_spans']} train-step span(s)")

        # un-awaited-future report (ISSUE 12): CollectiveLane clients'
        # created-vs-awaited future counts — the runtime companion of
        # static rule F002
        fw = _HOST_SYNC.future_report()
        per_class = ", ".join(
            f"{name}: {c['created']} created / {c['awaited']} awaited / "
            f"{c['resolved']} resolved"
            for name, c in fw["classes"].items()) or "no futures created"
        if fw["unawaited"]:
            terminalreporter.write_sep(
                "-", f"WARNING: future watch: {fw['unawaited']} lane "
                     "future(s) created but never awaited")
        terminalreporter.write_line(f"future watch: {per_class}")

    # lock-order witness report (only when FLAGS_lock_order_check ran)
    if _LOCK_ORDER is not None:
        rep = _LOCK_ORDER.get_graph().report()
        if rep["cycles"]:
            terminalreporter.write_sep(
                "-", f"WARNING: lock-order sanitizer found "
                     f"{len(rep['cycles'])} potential-deadlock cycle(s)")
            for c in rep["cycles"]:
                terminalreporter.write_line(
                    "  cycle: " + " -> ".join(c["nodes"] + [c["nodes"][0]]))
        else:
            terminalreporter.write_line(
                f"lock-order sanitizer: {_LOCK_ORDER.witness_count()} "
                f"witnessed lock(s), {rep['edge_count']} ordering edge(s) "
                f"across {len(rep['locks'])} lock(s), 0 cycles")
