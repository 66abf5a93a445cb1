"""Static-analysis suite + lock-order sanitizer (paddle_tpu/analysis, ISSUE 7).

Three layers of proof:
1. every checker rule has positive AND negative source fixtures;
2. the committed repo is clean against tools/static_baseline.json (and the
   baseline holds zero entries for the swallow/daemon/lock-discipline
   rules — those were fixed, not allowlisted);
3. the runtime lock-order witness reports a seeded ABBA inversion and
   stays silent on clean framework lock traffic.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.analysis import (  # noqa: E402
    RULES, analyze_sources, diff_against_baseline, findings_to_baseline,
    load_baseline, lock_order)


_REPO_RUN = None


def _repo_analysis():
    """One shared project-wide run for every repo-clean assertion (the
    full interprocedural pass costs ~3.5s; the new-rule tests reuse one
    result instead of re-running it per test)."""
    global _REPO_RUN
    if _REPO_RUN is None:
        from paddle_tpu.analysis import Analysis, default_checkers
        a = Analysis(default_checkers(), rel_root=REPO)
        findings = a.run_path(os.path.join(REPO, "paddle_tpu"))
        _REPO_RUN = (findings, a)
    return _REPO_RUN


def _rules(findings):
    return [f.rule for f in findings]


def _one(findings, rule):
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) == 1, f"expected exactly one {rule}, got {findings}"
    return hits[0]


# ---------------------------------------------------------------------------
# C001 — explicit daemon=
# ---------------------------------------------------------------------------

class TestDaemonRule:
    def test_flags_missing_daemon(self):
        src = "import threading\nt = threading.Thread(target=f)\n"
        f = _one(analyze_sources({"m.py": src}), "C001")
        assert f.line == 2

    def test_explicit_daemon_ok(self):
        src = ("import threading\n"
               "t = threading.Thread(target=f, daemon=True)\n"
               "u = threading.Thread(target=f, daemon=False)\n")
        assert "C001" not in _rules(analyze_sources({"m.py": src}))

    def test_kwargs_splat_not_flagged(self):
        src = "import threading\nt = threading.Thread(**kw)\n"
        assert "C001" not in _rules(analyze_sources({"m.py": src}))

    def test_repo_has_no_implicit_daemon_threads(self):
        """Satellite: every framework Thread states its shutdown contract."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "C001"] == []


# ---------------------------------------------------------------------------
# C002 — acquire/release discipline
# ---------------------------------------------------------------------------

class TestAcquireRule:
    def test_flags_bare_acquire(self):
        src = ("lock.acquire()\n"
               "x = 1\n"
               "lock.release()\n")
        f = _one(analyze_sources({"m.py": src}), "C002")
        assert "lock.acquire()" in f.message

    def test_try_finally_release_ok(self):
        src = ("try:\n"
               "    lock.acquire()\n"
               "    x = 1\n"
               "finally:\n"
               "    lock.release()\n")
        assert "C002" not in _rules(analyze_sources({"m.py": src}))

    def test_finally_releasing_other_lock_still_flagged(self):
        src = ("try:\n"
               "    a.acquire()\n"
               "finally:\n"
               "    b.release()\n")
        assert "C002" in _rules(analyze_sources({"m.py": src}))

    def test_acquire_as_condition_ok(self):
        # `if lock.acquire(timeout=1):` is the try-lock idiom, not a leak
        src = ("if lock.acquire(False):\n"
               "    lock.release()\n")
        assert "C002" not in _rules(analyze_sources({"m.py": src}))

    def test_with_statement_ok(self):
        src = "with lock:\n    x = 1\n"
        assert "C002" not in _rules(analyze_sources({"m.py": src}))


# ---------------------------------------------------------------------------
# C003 — no silent swallows
# ---------------------------------------------------------------------------

class TestSwallowRule:
    def test_flags_except_exception_pass(self):
        src = ("try:\n    f()\nexcept Exception:\n    pass\n")
        assert "C003" in _rules(analyze_sources({"m.py": src}))

    def test_flags_bare_except_pass(self):
        src = ("try:\n    f()\nexcept:\n    pass\n")
        assert "C003" in _rules(analyze_sources({"m.py": src}))

    def test_flags_base_exception_ellipsis(self):
        src = ("try:\n    f()\nexcept BaseException:\n    ...\n")
        assert "C003" in _rules(analyze_sources({"m.py": src}))

    def test_narrow_type_ok(self):
        src = ("try:\n    f()\nexcept OSError:\n    pass\n")
        assert "C003" not in _rules(analyze_sources({"m.py": src}))

    def test_recording_body_ok(self):
        src = ("try:\n    f()\nexcept Exception:\n    log.warning('x')\n")
        assert "C003" not in _rules(analyze_sources({"m.py": src}))

    def test_inline_waiver(self):
        src = ("try:\n    f()\n"
               "except Exception:   # lint-ok: C003 teardown guard\n"
               "    pass\n")
        assert "C003" not in _rules(analyze_sources({"m.py": src}))

    def test_repo_swallow_sites_are_fixed(self):
        """Satellite: the 9 seed `except Exception: pass` sites are gone
        (narrowed or recording), not baselined."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "C003"] == []


# ---------------------------------------------------------------------------
# C004 — lock-owning modules guard global writes
# ---------------------------------------------------------------------------

class TestGlobalMutationRule:
    LOCKED_MODULE = ("import threading\n"
                     "_lock = threading.Lock()\n"
                     "_state = None\n")

    def test_flags_unguarded_global_write(self):
        src = self.LOCKED_MODULE + (
            "def set_state(v):\n"
            "    global _state\n"
            "    _state = v\n")
        f = _one(analyze_sources({"m.py": src}), "C004")
        assert "_state" in f.message and "set_state" in f.message

    def test_guarded_write_ok(self):
        src = self.LOCKED_MODULE + (
            "def set_state(v):\n"
            "    global _state\n"
            "    with _lock:\n"
            "        _state = v\n")
        assert "C004" not in _rules(analyze_sources({"m.py": src}))

    def test_module_without_lock_not_flagged(self):
        src = ("_state = None\n"
               "def set_state(v):\n"
               "    global _state\n"
               "    _state = v\n")
        assert "C004" not in _rules(analyze_sources({"m.py": src}))

    def test_read_only_global_decl_ok(self):
        src = self.LOCKED_MODULE + (
            "def get_state():\n"
            "    global _state\n"
            "    return _state\n")
        assert "C004" not in _rules(analyze_sources({"m.py": src}))


# ---------------------------------------------------------------------------
# X001/X002/X003 — collective safety
# ---------------------------------------------------------------------------

class TestCollectiveSafety:
    def test_raw_primitive_outside_distributed_flagged(self):
        src = "import jax\ny = jax.lax.psum(x, 'dp')\n"
        f = _one(analyze_sources({"paddle_tpu/models/m.py": src}), "X001")
        assert "psum" in f.message

    def test_raw_primitive_inside_distributed_ok(self):
        src = "import jax\ny = jax.lax.psum(x, 'dp')\n"
        path = "paddle_tpu/distributed/ring.py"
        assert "X001" not in _rules(analyze_sources({path: src}))

    def test_execute_collective_outside_layer_flagged(self):
        src = ("from paddle_tpu.robustness.distributed_ft import "
               "execute_collective\n"
               "execute_collective('x', g, f)\n")
        found = analyze_sources({"paddle_tpu/io/m.py": src})
        assert _rules(found).count("X002") == 2  # import + call

    def test_eager_thunk_must_be_guarded(self):
        path = "paddle_tpu/distributed/collective.py"
        bad = ("def all_reduce(t):\n"
               "    def _eager():\n"
               "        return backend(t)\n"
               "    return _eager()\n")
        f = _one(analyze_sources({path: bad}), "X002")
        assert "_eager" in f.message
        good = ("def all_reduce(t):\n"
                "    def _eager():\n"
                "        return backend(t)\n"
                "    return _guarded('all_reduce', g, _eager)\n")
        assert "X002" not in _rules(analyze_sources({path: good}))

    def test_rank_conditional_collective_flagged(self):
        src = ("if get_rank() == 0:\n"
               "    dist.all_reduce(t)\n")
        f = _one(analyze_sources({"paddle_tpu/io/m.py": src}), "X003")
        assert "all_reduce" in f.message

    def test_rank_conditional_symmetric_ok(self):
        src = ("if get_rank() == 0:\n"
               "    dist.broadcast(t, src=0)\n"
               "else:\n"
               "    dist.broadcast(t, src=0)\n")
        assert "X003" not in _rules(
            analyze_sources({"paddle_tpu/io/m.py": src}))

    def test_rank_conditional_no_collective_ok(self):
        src = ("if get_rank() == 0:\n"
               "    print('hello from rank 0')\n")
        assert "X003" not in _rules(
            analyze_sources({"paddle_tpu/io/m.py": src}))


# ---------------------------------------------------------------------------
# T001 — trace purity
# ---------------------------------------------------------------------------

class TestTracePurity:
    def test_wallclock_in_jitted_fn_flagged(self):
        src = ("import jax, time\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    t = time.time()\n"
               "    return x + t\n")
        f = _one(analyze_sources({"m.py": src}), "T001")
        assert "time.time" in f.message and "step" in f.message

    def test_host_rng_in_scan_body_flagged(self):
        src = ("import jax, random\n"
               "def body(c, x):\n"
               "    return c + random.random(), x\n"
               "out = jax.lax.scan(body, 0.0, xs)\n")
        f = _one(analyze_sources({"m.py": src}), "T001")
        assert "random" in f.message

    def test_item_sync_in_shard_map_fn_flagged(self):
        src = ("def f(x):\n"
               "    return x.item()\n"
               "g = compat_shard_map(f, mesh, in_specs, out_specs)\n")
        assert "T001" in _rules(analyze_sources({"m.py": src}))

    def test_wallclock_outside_trace_ok(self):
        src = ("import time\n"
               "def host_step(x):\n"
               "    return time.time()\n")
        assert "T001" not in _rules(analyze_sources({"m.py": src}))

    def test_pure_traced_fn_ok(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return x * 2\n")
        assert "T001" not in _rules(analyze_sources({"m.py": src}))


# ---------------------------------------------------------------------------
# R001/R002 — registry drift
# ---------------------------------------------------------------------------

FLAGS_FIXTURE = ('_FLAGS = {\n'
                 '    "FLAGS_known": False,\n'
                 '}\n')


class TestRegistryDrift:
    def test_undeclared_flag_read_flagged(self):
        srcs = {
            "paddle_tpu/framework/flags.py": FLAGS_FIXTURE,
            "paddle_tpu/io/m.py": 'v = flag("FLAGS_mystery", 0)\n',
        }
        f = _one(analyze_sources(srcs), "R001")
        assert "FLAGS_mystery" in f.message

    def test_declared_flag_ok(self):
        srcs = {
            "paddle_tpu/framework/flags.py": FLAGS_FIXTURE,
            "paddle_tpu/io/m.py": 'v = flag("FLAGS_known", 0)\n',
        }
        assert "R001" not in _rules(analyze_sources(srcs))

    def test_repo_flags_all_declared(self):
        """FLAGS_selected_tpus was the live drift PR 7 found: read by
        distributed/env.py, set by launch/main.py, declared nowhere."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "R001"] == []
        from paddle_tpu.framework import flags
        assert "FLAGS_selected_tpus" in flags._FLAGS
        assert "FLAGS_lock_order_check" in flags._FLAGS

    def test_label_set_mismatch_at_bind_flagged(self):
        src = ('_m = reg.counter("x_total", labels=("op",))\n'
               '_m.labels(kind="y").inc()\n')
        f = _one(analyze_sources({"paddle_tpu/io/m.py": src}), "R002")
        assert "x_total" in f.message

    def test_matching_bind_ok(self):
        src = ('_m = reg.counter("x_total", labels=("op",))\n'
               '_m.labels(op="y").inc()\n'
               '_b = _m.bind(op="z")\n')
        assert "R002" not in _rules(
            analyze_sources({"paddle_tpu/io/m.py": src}))

    def test_conflicting_redeclaration_flagged(self):
        srcs = {
            "paddle_tpu/a.py": '_m = reg.counter("x_total", labels=("op",))\n',
            "paddle_tpu/b.py": '_m = reg.counter("x_total", labels=("kind",))\n',
        }
        assert "R002" in _rules(analyze_sources(srcs))


# ---------------------------------------------------------------------------
# F001 — path-aware lane-gather release (ISSUE 12, supersedes S001)
# ---------------------------------------------------------------------------

_F001_LEAKY = (
    "class Store:\n"
    "    def prefetch(self, i):\n"
    "        self._lane.submit(lambda: None)\n"
    "    def use(self, i):\n"
    "        self.ensure_gathered(i)\n"
    "        work(i)\n"
    "        self.free_bucket(i)\n"   # normal exit only — leaks on raise
)

_F001_CLEAN = (
    "class Store:\n"
    "    def prefetch(self, i):\n"
    "        self._lane.submit(lambda: None)\n"
    "    def use(self, i):\n"
    "        try:\n"
    "            self.ensure_gathered(i)\n"
    "            work(i)\n"
    "        finally:\n"
    "            self.free_bucket(i)\n"
)


class TestLaneGatherReleaseRule:
    def test_flags_unprotected_acquire_exception_path(self):
        # old S001 shape: no finally — now flagged WITH the leaking path
        f = _one(analyze_sources({"m.py": _F001_LEAKY}), "F001")
        assert "path" in f.message and "use()" in f.message

    def test_release_in_finally_ok(self):
        assert "F001" not in _rules(analyze_sources({"m.py": _F001_CLEAN}))

    def test_early_return_between_acquire_and_release_flagged(self):
        src = (
            "class Store:\n"
            "    def prefetch(self, i):\n"
            "        self._lane.submit(lambda: None)\n"
            "    def use(self, i):\n"
            "        try:\n"
            "            self.ensure_gathered(i)\n"
            "            if bad():\n"
            "                return None\n"          # leaks: skips finally?
            "            out = work(i)\n"
            "        finally:\n"
            "            pass\n"
            "        self.free_bucket(i)\n"
            "        return out\n")
        # the finally releases NOTHING; both the return path and the
        # exception path leak
        f = _one(analyze_sources({"m.py": src}), "F001")
        assert "free/release" in f.message

    def test_handler_return_without_release_flagged(self):
        src = (
            "class Store:\n"
            "    def prefetch(self, i):\n"
            "        self._lane.submit(lambda: None)\n"
            "    def use(self, i):\n"
            "        self.ensure_gathered(i)\n"
            "        try:\n"
            "            work(i)\n"
            "        except Exception:\n"
            "            return None\n"              # exception path leaks
            "        self.free_bucket(i)\n")
        assert "F001" in _rules(analyze_sources({"m.py": src}))

    def test_release_loop_in_finally_discharges_acquire_loop(self):
        # the stage3 materialize() shape: acquire-loop in try, free-loop
        # in finally — the loop-head kill lift must prove it clean
        src = (
            "class Store:\n"
            "    def prefetch(self, i):\n"
            "        self._lane.submit(lambda: None)\n"
            "    def use_all(self):\n"
            "        try:\n"
            "            for b in self.buckets:\n"
            "                self.ensure_gathered(b.index)\n"
            "            work()\n"
            "        finally:\n"
            "            for b in self.buckets:\n"
            "                self.free_bucket(b.index)\n")
        assert "F001" not in _rules(analyze_sources({"m.py": src}))

    def test_module_with_no_release_anywhere_flagged(self):
        # S001's module-level verdict survives the supersession
        src = ("class Store:\n"
               "    def prefetch(self, i):\n"
               "        self._lane.submit(lambda: None)\n"
               "    def use(self, i):\n"
               "        self.ensure_gathered(i)\n")
        f = _one(analyze_sources({"m.py": src}), "F001")
        assert "no free/release call at all" in f.message

    def test_s001_waiver_still_suppresses(self):
        src = ("class Store:\n"
               "    def prefetch(self, i):\n"
               "        self._lane.submit(lambda: None)\n"
               "    def use(self, i):\n"
               "        self.ensure_gathered(i)  "
               "# lint-ok: S001 legacy waiver\n")
        assert "F001" not in _rules(analyze_sources({"m.py": src}))

    def test_lane_submit_without_gathers_not_flagged(self):
        # the grad lane (overlap.py shape): submits, but never acquires
        # gathered buffers — not a gather client
        src = ("class Comm:\n"
               "    def launch(self, b):\n"
               "        self._lane.submit(lambda: None)\n")
        assert "F001" not in _rules(analyze_sources({"m.py": src}))

    def test_gathers_without_lane_not_flagged(self):
        # ensure/free helpers with no lane in sight are out of scope
        src = ("def f(s):\n"
               "    s.ensure_gathered(0)\n")
        assert "F001" not in _rules(analyze_sources({"m.py": src}))

    def test_ownership_transfer_functions_skipped(self):
        # acquire with no local release = store pattern (a later hook
        # frees) — out of scope by design
        src = ("class Store:\n"
               "    def prefetch(self, i):\n"
               "        self._lane.submit(lambda: None)\n"
               "    def pre_hook(self, i):\n"
               "        self.ensure_gathered(i)\n"
               "    def post_hook(self, i):\n"
               "        self.free_bucket(i)\n")
        assert "F001" not in _rules(analyze_sources({"m.py": src}))

    def test_stage3_store_is_clean(self):
        """The real lane gather client (distributed/sharding/stage3.py)
        carries the all-paths release: materialize()'s finally proves
        clean under the PATH-aware rule."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule in ("F001", "S001")] == []


# ---------------------------------------------------------------------------
# S002 — signal handlers only set flags/latches
# ---------------------------------------------------------------------------

_S002_LOGGING = (
    "import logging\n"
    "import signal\n"
    "def handler(signum, frame):\n"
    "    logging.getLogger(__name__).warning('preempted %s', signum)\n"
    "signal.signal(signal.SIGTERM, handler)\n"
)

_S002_LOCK = (
    "import signal\n"
    "class H:\n"
    "    def _on_term(self, signum, frame):\n"
    "        self._lock.acquire()\n"
    "        self.preempted = True\n"
    "    def install(self):\n"
    "        signal.signal(signal.SIGTERM, self._on_term)\n"
)

_S002_CLEAN = (
    "import signal\n"
    "class H:\n"
    "    def _handler(self, signum, frame):\n"
    "        self._signum = signum\n"
    "        self._latch.set()\n"
    "    def install(self):\n"
    "        signal.signal(signal.SIGTERM, self._handler)\n"
)


class TestSignalSafetyRule:
    def test_flags_logging_in_handler(self):
        f = _one(analyze_sources({"m.py": _S002_LOGGING}), "S002")
        assert "handler" in f.message and "latch" in f.message

    def test_flags_lock_acquire_in_method_handler(self):
        f = _one(analyze_sources({"m.py": _S002_LOCK}), "S002")
        assert "_on_term" in f.message

    def test_latch_only_body_ok(self):
        assert "S002" not in _rules(analyze_sources({"m.py": _S002_CLEAN}))

    def test_lambda_handlers_checked(self):
        bad = ("import signal\n"
               "signal.signal(signal.SIGTERM, lambda s, f: print(s))\n")
        assert "S002" in _rules(analyze_sources({"m.py": bad}))
        ok = ("import signal\n"
              "signal.signal(signal.SIGTERM, lambda s, f: latch.set())\n")
        assert "S002" not in _rules(analyze_sources({"m.py": ok}))

    def test_unresolvable_handler_skipped(self):
        # an imported/dynamic handler cannot be analyzed here — no false
        # positive
        src = ("import signal\n"
               "from other import handler\n"
               "signal.signal(signal.SIGTERM, handler)\n")
        assert "S002" not in _rules(analyze_sources({"m.py": src}))

    def test_send_signal_is_not_registration(self):
        # launch/main.py shape: SENDING a signal is not registering a
        # handler
        src = ("import signal\n"
               "def stop(q):\n"
               "    q.send_signal(signal.SIGTERM)\n")
        assert "S002" not in _rules(analyze_sources({"m.py": src}))

    def test_repo_handlers_are_latch_only(self):
        """The real PreemptionHandler (robustness/preemption.py) obeys its
        own contract — the repo stays S002-clean."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "S002"] == []


# ---------------------------------------------------------------------------
# engine: baseline diff + waivers
# ---------------------------------------------------------------------------

class TestEngine:
    def test_baseline_roundtrip_clean(self):
        src = {"m.py": "import threading\nt = threading.Thread(target=f)\n"}
        findings = analyze_sources(src)
        baseline = findings_to_baseline(findings)["entries"]
        new, stale = diff_against_baseline(findings, baseline)
        assert new == [] and stale == []

    def test_new_finding_detected(self):
        src = {"m.py": "import threading\nt = threading.Thread(target=f)\n"}
        new, stale = diff_against_baseline(analyze_sources(src), [])
        assert len(new) == 1 and stale == []

    def test_stale_entry_detected(self):
        ghost = [{"rule": "C001", "path": "gone.py",
                  "message": "threading.Thread(...) without explicit daemon="}]
        new, stale = diff_against_baseline([], ghost)
        assert new == [] and len(stale) == 1

    def test_multiplicity_matters(self):
        src = {"m.py": ("import threading\n"
                        "t = threading.Thread(target=f)\n"
                        "u = threading.Thread(target=f)\n")}
        findings = analyze_sources(src)
        assert len(findings) == 2
        one = findings_to_baseline(findings[:1])["entries"]
        new, stale = diff_against_baseline(findings, one)
        assert len(new) == 1 and stale == []

    def test_every_rule_documented(self):
        for rule in ("C001", "C002", "C003", "C004", "X001", "X002", "X003",
                     "X004", "X005", "T001", "T002", "T003", "R001", "R002",
                     "S001", "S002", "D001", "D002", "F001", "F002", "F003",
                     "F004", "F005", "F006"):
            assert rule in RULES
            invariant, rationale = RULES[rule]
            assert invariant and rationale

    def test_s001_documented_as_superseded(self):
        """Satellite (ISSUE 12): the rule id stays live as an alias with
        its supersession recorded in RULES."""
        assert "superseded by F001" in RULES["S001"][0]


# ---------------------------------------------------------------------------
# call graph / symbol table (ISSUE 11 tentpole)
# ---------------------------------------------------------------------------

class TestCallGraph:
    def _index(self, sources):
        from paddle_tpu.analysis import Analysis, default_checkers
        a = Analysis(default_checkers())
        a.run_sources(sources)
        return a.index

    def test_cross_module_reachability(self):
        idx = self._index({
            "paddle_tpu/a.py": ("from paddle_tpu.b import middle\n"
                                "def top():\n"
                                "    return middle()\n"),
            "paddle_tpu/b.py": ("def middle():\n"
                                "    return _leaf()\n"
                                "def _leaf():\n"
                                "    return 1\n"),
        })
        reach = idx.reachable("paddle_tpu/a.py::top")
        assert "paddle_tpu/b.py::middle" in reach
        assert "paddle_tpu/b.py::_leaf" in reach

    def test_relative_import_resolution(self):
        idx = self._index({
            "paddle_tpu/pkg/a.py": ("from .b import helper\n"
                                    "def f():\n"
                                    "    return helper()\n"),
            "paddle_tpu/pkg/b.py": "def helper():\n    return 2\n",
        })
        assert "paddle_tpu/pkg/b.py::helper" in \
            idx.reachable("paddle_tpu/pkg/a.py::f")

    def test_self_method_edges(self):
        idx = self._index({
            "m.py": ("class C:\n"
                     "    def run(self):\n"
                     "        return self._impl()\n"
                     "    def _impl(self):\n"
                     "        return 0\n"),
        })
        assert idx.callees("m.py::C.run") == ("m.py::C._impl",)

    def test_nested_def_implicit_edge(self):
        idx = self._index({
            "m.py": ("def outer():\n"
                     "    def inner():\n"
                     "        return 1\n"
                     "    return inner\n"),
        })
        assert "m.py::outer.inner" in idx.reachable("m.py::outer")

    def test_fallback_requires_unique_name(self):
        srcs = {
            "a.py": "class A:\n    def unique_leaf(self):\n        return 1\n",
            "b.py": "def caller(obj):\n    return obj.unique_leaf()\n",
        }
        idx = self._index(srcs)
        assert idx.reachable("b.py::caller") == {"a.py::A.unique_leaf"}
        # confident-only traversal must NOT take the fallback edge
        assert idx.reachable("b.py::caller", fallback=False) == set()
        # a second function with the same bare name kills the fallback
        srcs["c.py"] = "def unique_leaf():\n    return 2\n"
        idx2 = self._index(srcs)
        assert idx2.reachable("b.py::caller") == set()

    def test_module_of_paths(self):
        from paddle_tpu.analysis.callgraph import module_of
        assert module_of("paddle_tpu/distributed/collective.py") == \
            "paddle_tpu.distributed.collective"
        assert module_of("paddle_tpu/analysis/__init__.py") == \
            "paddle_tpu.analysis"

    def test_repo_index_scales(self):
        """The index answers reachability over the real tree: the public
        all_reduce is reachable from the sanctioned in-trace helper's
        module peers (gpt's manual-SPMD forward)."""
        _, a = _repo_analysis()
        idx = a.index
        assert len(idx.functions) > 1000   # the whole framework is indexed
        # any gpt module function using the helper reaches collective.py
        gpt_fns = [fn for fn in idx.functions
                   if fn.startswith("paddle_tpu/models/gpt.py::")]
        assert gpt_fns
        hit = any(
            any(c.startswith("paddle_tpu/distributed/collective.py::")
                for c in idx.reachable(fn))
            for fn in gpt_fns)
        assert hit


# ---------------------------------------------------------------------------
# D001/D002 — donation safety (ISSUE 11)
# ---------------------------------------------------------------------------

# the PR-8 TrainStep donation-alias bug, reduced to its pre-fix shape:
# donated params/slots pair AFTER the batch-sharded out_vals in the
# return tuple, so a same-shape batch output steals the alias slot
_D002_PREFIX_BUG = """
import jax

def pure_step(train_p, slots, in_vals):
    out_vals = forward(in_vals)
    loss, grads = value_and_grad_of(train_p, in_vals)
    new_tp = update(train_p, grads)
    new_slots = tick(slots)
    return loss, out_vals, new_tp, new_slots

step = jax.jit(pure_step, donate_argnums=(0, 1))
"""

_D002_FIXED = _D002_PREFIX_BUG.replace(
    "return loss, out_vals, new_tp, new_slots",
    "return loss, new_tp, new_slots, out_vals")


class TestDonationRules:
    def test_d002_flags_pr8_prefix_shape(self):
        f = _one(analyze_sources({"m.py": _D002_PREFIX_BUG}), "D002")
        assert "pure_step" in f.message and "alias" in f.message

    def test_d002_fixed_order_clean(self):
        assert "D002" not in _rules(analyze_sources({"m.py": _D002_FIXED}))

    def test_d002_decorator_partial_form(self):
        src = ("import jax\n"
               "from functools import partial\n"
               "@partial(jax.jit, donate_argnums=(0,))\n"
               "def step(params, batch):\n"
               "    out = fwd(batch)\n"
               "    new_p = upd(params)\n"
               "    return out, new_p\n")
        assert "D002" in _rules(analyze_sources({"m.py": src}))

    def test_d002_all_donated_derived_clean(self):
        # the real TrainStep shape: loss derives from train_p too, so no
        # element is a PURE batch output before the donated ones
        src = ("import jax\n"
               "def step(p, x):\n"
               "    loss, new_p = upd(p, x)\n"
               "    return loss, new_p\n"
               "f = jax.jit(step, donate_argnums=(0,))\n")
        assert "D002" not in _rules(analyze_sources({"m.py": src}))

    def test_d001_read_after_donation_flagged(self):
        src = ("import jax\n"
               "def run(params, x):\n"
               "    step = jax.jit(update, donate_argnums=(0,))\n"
               "    out = step(params, x)\n"
               "    return params + out\n")
        f = _one(analyze_sources({"m.py": src}), "D001")
        assert "params" in f.message

    def test_d001_rebind_idiom_clean(self):
        src = ("import jax\n"
               "def run(params, x):\n"
               "    step = jax.jit(update, donate_argnums=(0,))\n"
               "    params = step(params, x)\n"
               "    return params\n")
        assert "D001" not in _rules(analyze_sources({"m.py": src}))

    def test_d001_non_donated_arg_ok(self):
        src = ("import jax\n"
               "def run(params, x):\n"
               "    step = jax.jit(update, donate_argnums=(0,))\n"
               "    params = step(params, x)\n"
               "    return x\n")   # x was position 1: not donated
        assert "D001" not in _rules(analyze_sources({"m.py": src}))

    def test_d001_direct_call_form(self):
        src = ("import jax\n"
               "def run(params, x):\n"
               "    out = jax.jit(update, donate_argnums=(0,))(params, x)\n"
               "    return params\n")
        assert "D001" in _rules(analyze_sources({"m.py": src}))

    def test_repo_clean_on_donation_rules(self):
        """Acceptance: the repo (incl. the PR-8-fixed TrainStep and the
        static-graph executor's train_fn) is D001/D002-clean."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule in ("D001", "D002")] == []


# ---------------------------------------------------------------------------
# X004 — interprocedural SPMD consistency (ISSUE 11)
# ---------------------------------------------------------------------------

class TestInterproceduralSPMD:
    def test_transitive_collective_in_one_arm_flagged(self):
        src = ("def _commit(t):\n"
               "    dist.all_reduce(t)\n"
               "def save(t):\n"
               "    if get_rank() == 0:\n"
               "        _commit(t)\n")
        f = _one(analyze_sources({"paddle_tpu/io/m.py": src}), "X004")
        assert "_commit" in f.message and "all_reduce" in f.message

    def test_two_hop_chain_flagged(self):
        src = ("def _inner(t):\n"
               "    dist.barrier()\n"
               "def _outer(t):\n"
               "    _inner(t)\n"
               "def save(t):\n"
               "    if get_rank() == 0:\n"
               "        _outer(t)\n")
        assert "X004" in _rules(analyze_sources({"paddle_tpu/io/m.py": src}))

    def test_symmetric_transitive_ok(self):
        src = ("def _commit(t):\n"
               "    dist.all_reduce(t)\n"
               "def save(t):\n"
               "    if get_rank() == 0:\n"
               "        _commit(t)\n"
               "    else:\n"
               "        _commit(t)\n")
        assert "X004" not in _rules(
            analyze_sources({"paddle_tpu/io/m.py": src}))

    def test_helper_without_collective_ok(self):
        src = ("def _log(t):\n"
               "    print(t)\n"
               "def save(t):\n"
               "    if get_rank() == 0:\n"
               "        _log(t)\n")
        assert "X004" not in _rules(
            analyze_sources({"paddle_tpu/io/m.py": src}))

    def test_direct_collective_stays_x003(self):
        # the direct form is X003's; X004 must not double-report it
        src = ("if get_rank() == 0:\n"
               "    dist.all_reduce(t)\n")
        found = analyze_sources({"paddle_tpu/io/m.py": src})
        assert _rules(found).count("X003") == 1
        assert "X004" not in _rules(found)

    def test_generic_send_leaf_not_transitive(self):
        # a rank-gated helper calling socket/bus .send() is host-side
        # point-to-point, not an SPMD collective
        src = ("def _notify(bus, t):\n"
               "    bus.send(t)\n"
               "def save(bus, t):\n"
               "    if get_rank() == 0:\n"
               "        _notify(bus, t)\n")
        assert "X004" not in _rules(
            analyze_sources({"paddle_tpu/io/m.py": src}))

    def test_repo_clean_on_x004(self):
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "X004"] == []


# ---------------------------------------------------------------------------
# T003 — transitive trace purity (ISSUE 11)
# ---------------------------------------------------------------------------

class TestTransitiveTracePurity:
    def test_impurity_one_call_away_flagged(self):
        src = ("import jax, time\n"
               "def _helper(x):\n"
               "    return x + time.time()\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _helper(x)\n")
        f = _one(analyze_sources({"m.py": src}), "T003")
        assert "step" in f.message and "time.time" in f.message \
            and "_helper" in f.message

    def test_chain_reported_in_message(self):
        src = ("import jax, time\n"
               "def _deeper(x):\n"
               "    time.sleep(0)\n"
               "    return x\n"
               "def _helper(x):\n"
               "    return _deeper(x)\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _helper(x)\n")
        f = _one(analyze_sources({"m.py": src}), "T003")
        assert "_helper -> _deeper" in f.message

    def test_direct_impurity_stays_t001(self):
        src = ("import jax, time\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return x + time.time()\n")
        found = analyze_sources({"m.py": src})
        assert "T001" in _rules(found) and "T003" not in _rules(found)

    def test_in_trace_guard_is_trusted_boundary(self):
        # the collective layer's dual-path contract: a callee that
        # branches on _in_trace handles both worlds itself
        src = ("import jax, time\n"
               "def _dual(x):\n"
               "    if _in_trace(x):\n"
               "        return x\n"
               "    return x + time.time()\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _dual(x)\n")
        assert "T003" not in _rules(analyze_sources({"m.py": src}))

    def test_pure_helpers_clean(self):
        src = ("import jax\n"
               "def _helper(x):\n"
               "    return x * 2\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return _helper(x)\n")
        assert "T003" not in _rules(analyze_sources({"m.py": src}))

    def test_repo_clean_on_t003(self):
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "T003"] == []


# ---------------------------------------------------------------------------
# stale-waiver hygiene (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

class TestStaleWaivers:
    def _run(self, sources):
        from paddle_tpu.analysis import Analysis, default_checkers
        a = Analysis(default_checkers())
        findings = a.run_sources(sources)
        return findings, a.stale_waivers

    def test_dead_waiver_reported(self):
        _, stale = self._run({"m.py": "x = 1  # lint-ok: C003 obsolete\n"})
        assert stale == [{"path": "m.py", "line": 1, "rule": "C003"}]

    def test_live_waiver_not_stale(self):
        src = ("try:\n    f()\n"
               "except Exception:   # lint-ok: C003 teardown guard\n"
               "    pass\n")
        findings, stale = self._run({"m.py": src})
        assert "C003" not in _rules(findings)
        assert stale == []

    def test_multi_rule_waiver_partial_staleness(self):
        # C003 fires (and is waived); C001 never fires on that line
        src = ("try:\n    f()\n"
               "except Exception:   # lint-ok: C003, C001 both?\n"
               "    pass\n")
        _, stale = self._run({"m.py": src})
        assert stale == [{"path": "m.py", "line": 3, "rule": "C001"}]

    def test_docstring_mention_is_not_a_waiver(self):
        src = ('"""Docs: a line ending in ``# lint-ok: C003 x`` waives."""\n'
               "x = 1\n")
        _, stale = self._run({"m.py": src})
        assert stale == []

    def test_repo_has_no_stale_waivers(self):
        _, a = _repo_analysis()
        assert a.stale_waivers == []

    def test_gate_exit_2_on_stale_waiver(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text("x = 1  # lint-ok: C001 dead comment\n")
        bl = tmp_path / "bl.json"
        bl.write_text('{"entries": []}')
        spec = importlib.util.spec_from_file_location(
            "check_static", os.path.join(REPO, "tools", "check_static.py"))
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        rc = cs.main(["--root", str(tmp_path), "--baseline", str(bl),
                      "--no-cache"])
        assert rc == 2


# ---------------------------------------------------------------------------
# the tier-1 gate itself
# ---------------------------------------------------------------------------

class TestCheckStaticGate:
    def _main(self):
        spec = importlib.util.spec_from_file_location(
            "check_static", os.path.join(REPO, "tools", "check_static.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main

    def test_repo_clean_against_committed_baseline(self):
        assert self._main()([]) == 0

    def test_baseline_has_no_allowlisted_discipline_findings(self):
        """Acceptance: swallow/daemon/lock-discipline entries were FIXED,
        so the baseline holds zero of them."""
        entries = load_baseline(
            os.path.join(REPO, "tools", "static_baseline.json"))
        rules_in_baseline = {e["rule"] for e in entries}
        assert rules_in_baseline.isdisjoint({"C001", "C002", "C003"})
        for e in entries:       # remaining debt is documented
            assert e.get("reason"), f"baseline entry missing reason: {e}"

    def test_exit_1_on_new_finding(self, tmp_path):
        bad = tmp_path / "m.py"
        bad.write_text("import threading\nt = threading.Thread(target=f)\n")
        empty = tmp_path / "baseline.json"
        empty.write_text('{"entries": []}')
        rc = self._main()(["--root", str(tmp_path),
                           "--baseline", str(empty)])
        assert rc == 1

    def test_exit_2_on_stale_entry(self, tmp_path):
        clean = tmp_path / "m.py"
        clean.write_text("x = 1\n")
        stale = tmp_path / "baseline.json"
        stale.write_text(json.dumps({"entries": [{
            "rule": "C001", "path": "m.py", "line": 1,
            "message": "threading.Thread(...) without explicit daemon="}]}))
        rc = self._main()(["--root", str(tmp_path),
                           "--baseline", str(stale)])
        assert rc == 2

    def test_cli_exit_code(self):
        """The committed gate command CI runs, end to end."""
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check_static.py")],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "OK: clean against baseline" in p.stdout


# ---------------------------------------------------------------------------
# lock-order sanitizer
# ---------------------------------------------------------------------------

class TestLockOrder:
    def test_seeded_abba_inversion_detected(self):
        g = lock_order.LockOrderGraph()
        A = lock_order.WitnessLock(threading.Lock(), "A", g)
        B = lock_order.WitnessLock(threading.Lock(), "B", g)
        with A:
            with B:
                pass
        with B:        # the inversion — never actually deadlocks here,
            with A:    # but the ORDER violation is still witnessed
                pass
        cycles = g.cycles()
        assert cycles == [["A", "B"]]
        rep = g.report()
        assert rep["cycle_lock_names"] == ["A", "B"]
        edge = rep["cycles"][0]["edges"][0]
        assert edge["count"] >= 1 and edge["thread"]

    def test_three_lock_cycle_detected(self):
        g = lock_order.LockOrderGraph()
        a, b, c = (lock_order.WitnessLock(threading.Lock(), n, g)
                   for n in "abc")
        for first, second in ((a, b), (b, c), (c, a)):
            with first:
                with second:
                    pass
        assert g.cycles() == [["a", "b", "c"]]

    def test_consistent_order_is_silent(self):
        g = lock_order.LockOrderGraph()
        A = lock_order.WitnessLock(threading.Lock(), "A", g)
        B = lock_order.WitnessLock(threading.Lock(), "B", g)
        for _ in range(3):
            with A:
                with B:
                    pass
        assert g.cycles() == []
        assert g.report()["edge_count"] == 1

    def test_cross_thread_edges_recorded(self):
        g = lock_order.LockOrderGraph()
        A = lock_order.WitnessLock(threading.Lock(), "A", g)
        B = lock_order.WitnessLock(threading.Lock(), "B", g)

        def t1():
            with A:
                with B:
                    pass

        def t2():
            with B:
                with A:
                    pass

        th1 = threading.Thread(target=t1, daemon=True)
        th1.start(); th1.join()
        th2 = threading.Thread(target=t2, daemon=True)
        th2.start(); th2.join()
        assert g.cycles() == [["A", "B"]]

    def test_release_out_of_order(self):
        g = lock_order.LockOrderGraph()
        A = lock_order.WitnessLock(threading.Lock(), "A", g)
        B = lock_order.WitnessLock(threading.Lock(), "B", g)
        A.acquire(); B.acquire()
        A.release(); B.release()     # non-LIFO release must not corrupt
        with B:
            pass
        assert g.cycles() == []

    def test_works_as_condition_lock(self):
        g = lock_order.LockOrderGraph()
        w = lock_order.WitnessLock(threading.Lock(), "cv", g)
        cv = threading.Condition(w)
        hits = []

        def waiter():
            with cv:
                cv.wait(timeout=5)
                hits.append(1)

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.05)
        with cv:
            cv.notify_all()
        t.join(timeout=5)
        assert hits == [1]

    def test_install_instruments_only_paddle_tpu_locks(self):
        g = lock_order.LockOrderGraph()
        was_installed = lock_order.installed()
        lock_order.uninstall()
        lock_order.install(g)
        try:
            here = threading.Lock()           # test file: raw
            assert not isinstance(here, lock_order.WitnessLock)
            ns = {}
            code = compile("import threading\nL = threading.Lock()\n",
                           "/x/paddle_tpu/fake/mod.py", "exec")
            exec(code, ns)
            assert isinstance(ns["L"], lock_order.WitnessLock)
            assert "paddle_tpu/fake/mod.py" in ns["L"].name
        finally:
            lock_order.uninstall()
            if was_installed:      # restore the session-level witness
                lock_order.install()

    def test_clean_on_real_framework_traffic(self):
        """Silence proof: when tier-1 runs with FLAGS_lock_order_check the
        global graph must hold no cycles; otherwise exercise real lock
        nesting (collective lane + event log + metrics) under a local
        install and prove the same."""
        if lock_order.installed():
            assert lock_order.get_graph().cycles() == []
            return
        g = lock_order.LockOrderGraph()
        lock_order.install(g)
        try:
            ns = {}
            code = compile(
                "import threading\n"
                "outer = threading.Lock()\n"
                "inner = threading.Lock()\n",
                "/x/paddle_tpu/fake/lane.py", "exec")
            exec(code, ns)
            from paddle_tpu.distributed.overlap import CollectiveLane
            from paddle_tpu.observability.events import get_event_log
            lane = CollectiveLane(name="sanitizer-test-lane")
            done = []
            for i in range(4):
                def job(i=i):
                    with ns["outer"]:
                        with ns["inner"]:
                            get_event_log().debug("sanitizer", f"job{i}")
                    done.append(i)
                lane.submit(job)
            deadline = time.time() + 10
            while len(done) < 4 and time.time() < deadline:
                time.sleep(0.01)
            assert len(done) == 4
            assert g.cycles() == []
        finally:
            lock_order.uninstall()

    def test_thread_leak_report(self):
        stop = threading.Event()
        t = threading.Thread(target=stop.wait, name="leaky-nondaemon",
                             daemon=False)
        t.start()
        try:
            leaks = lock_order.thread_leak_report(set())
            assert any(l["name"] == "leaky-nondaemon" for l in leaks)
        finally:
            stop.set()
            t.join(timeout=5)
        leaks = lock_order.thread_leak_report(set())
        assert not any(l["name"] == "leaky-nondaemon" for l in leaks)

    def test_flag_installs_witness(self):
        """set_flags({'FLAGS_lock_order_check': True}) wires install()."""
        import paddle_tpu
        was = lock_order.installed()
        try:
            paddle_tpu.set_flags({"FLAGS_lock_order_check": True})
            assert lock_order.installed()
        finally:
            if not was:
                lock_order.uninstall()
            paddle_tpu.set_flags({"FLAGS_lock_order_check": was})


# ---------------------------------------------------------------------------
# gate modes: --changed-only / --sarif / AST cache / wall budget (ISSUE 11)
# ---------------------------------------------------------------------------

class TestGateModes:
    def _main(self):
        spec = importlib.util.spec_from_file_location(
            "check_static", os.path.join(REPO, "tools", "check_static.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_changed_only_reports_only_changed_files(self, tmp_path):
        """A tmp git repo with a committed dirty file and a NEW dirty
        file: --changed-only must report only the new one."""
        repo = tmp_path / "r"
        repo.mkdir()

        def git(*args):
            subprocess.run(["git", "-c", "user.email=t@t",
                            "-c", "user.name=t", *args],
                           cwd=repo, check=True, capture_output=True)

        git("init", "-q", ".")
        # committed file carries a violation that predates the change set
        (repo / "old.py").write_text(
            "import threading\nt = threading.Thread(target=f)\n")
        git("add", "old.py")
        git("commit", "-qm", "init")
        (repo / "new.py").write_text(
            "import threading\nu = threading.Thread(target=f)\n")
        bl = tmp_path / "bl.json"
        bl.write_text('{"entries": []}')
        cs = self._main()
        rc = cs.main(["--root", str(repo), "--baseline", str(bl),
                      "--changed-only", "HEAD", "--no-cache", "--json"])
        assert rc == 1   # new.py's finding is new
        # full run sees both files' findings
        rc_full = cs.main(["--root", str(repo), "--baseline", str(bl),
                           "--no-cache"])
        assert rc_full == 1

    def test_changed_only_scopes_the_baseline(self, tmp_path, capsys):
        repo = tmp_path / "r"
        repo.mkdir()

        def git(*args):
            subprocess.run(["git", "-c", "user.email=t@t",
                            "-c", "user.name=t", *args],
                           cwd=repo, check=True, capture_output=True)

        git("init", "-q", ".")
        (repo / "old.py").write_text(
            "import threading\nt = threading.Thread(target=f)\n")
        git("add", "old.py")
        git("commit", "-qm", "init")
        (repo / "new.py").write_text("x = 1\n")
        # old.py's finding is baselined; old.py is NOT in the change set,
        # so neither its finding nor its baseline entry participates
        bl = tmp_path / "bl.json"
        bl.write_text(json.dumps({"entries": [{
            "rule": "C001", "path": "old.py", "line": 2,
            "message": "threading.Thread(...) without explicit daemon="}]}))
        cs = self._main()
        rc = cs.main(["--root", str(repo), "--baseline", str(bl),
                      "--changed-only", "HEAD", "--no-cache"])
        capsys.readouterr()
        assert rc == 0

    def test_sarif_output_shape(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text("import threading\nt = threading.Thread(target=f)\n")
        bl = tmp_path / "bl.json"
        bl.write_text('{"entries": []}')
        sarif = tmp_path / "out.sarif"
        cs = self._main()
        rc = cs.main(["--root", str(tmp_path), "--baseline", str(bl),
                      "--no-cache", "--sarif", str(sarif)])
        assert rc == 1
        doc = json.loads(sarif.read_text())
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "paddle_tpu.analysis"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"C001", "D002", "X004", "T003"} <= rule_ids
        res = run["results"]
        assert len(res) == 1 and res[0]["ruleId"] == "C001"
        loc = res[0]["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "m.py"
        assert loc["region"]["startLine"] == 2

    def test_ast_cache_roundtrip(self, tmp_path):
        from paddle_tpu.analysis import AstCache
        mod = tmp_path / "m.py"
        mod.write_text("x = 1\n")
        cache_path = str(tmp_path / "cache.pkl")
        c1 = AstCache(cache_path)
        src, tree = c1.get(str(mod), "m.py")
        assert c1.misses == 1 and c1.hits == 0
        c1.save()
        c2 = AstCache(cache_path)
        src2, tree2 = c2.get(str(mod), "m.py")
        assert c2.hits == 1 and c2.misses == 0
        assert src2 == src
        # an edit invalidates the entry
        mod.write_text("x = 2\n")
        c3 = AstCache(cache_path)
        c3.get(str(mod), "m.py")
        assert c3.misses == 1
        # a corrupt cache file is ignored, not fatal
        with open(cache_path, "wb") as f:
            f.write(b"not a pickle")
        c4 = AstCache(cache_path)
        c4.get(str(mod), "m.py")
        assert c4.misses == 1

    def test_full_run_wall_within_budget(self, tmp_path, capsys):
        """The full interprocedural run over the repo is clean, and its
        steady state does the work the budget was set for, by count: with
        the AST cache of a first run, a second run parses no file (every
        file analysed is a cache hit). A run that re-parses the tree, the
        regression the 8 s budget of ISSUE 11 stood guard over, fails here
        whatever the machine's load."""
        import importlib.util as iu
        spec = iu.spec_from_file_location(
            "check_static", os.path.join(REPO, "tools", "check_static.py"))
        cs = iu.module_from_spec(spec)
        spec.loader.exec_module(cs)
        cache_path = str(tmp_path / "static_ast.pkl")

        def run():
            capsys.readouterr()
            rc = cs.main(["--json", "--cache-path", cache_path])
            doc, _ = json.JSONDecoder().raw_decode(
                capsys.readouterr().out.lstrip())
            return rc, doc["cache"]

        from paddle_tpu.analysis.engine import _iter_py_files

        n_files = len(_iter_py_files(os.path.join(REPO, "paddle_tpu")))
        rc, cold = run()
        assert rc == 0
        assert cold == {"hits": 0, "misses": n_files}
        rc, warm = run()
        assert rc == 0
        assert warm == {"hits": n_files, "misses": 0}


# ---------------------------------------------------------------------------
# X001 burn-down: the baseline holds ZERO entries (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

class TestX001BurnDown:
    def test_repo_has_no_raw_lax_collectives_outside_distributed(self):
        """gpt's six waived TP psum/pmax sites now ride the sanctioned
        in-trace helpers (distributed.collective.in_trace_psum/pmax)."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "X001"] == []

    def test_baseline_is_empty(self):
        entries = load_baseline(
            os.path.join(REPO, "tools", "static_baseline.json"))
        assert entries == []

    def test_in_trace_helpers_record_and_reduce(self):
        """The sanctioned helpers lower to the same lax collectives and
        tick the per-op counters at trace time."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.distributed import collective as coll
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.observability.metrics import get_registry

        m = mesh_mod.default_mesh()
        axis = m.axis_names[0]
        n = m.shape[axis]

        def psum_count():
            snap = get_registry().snapshot().get("collectives_total", {})
            return snap.get("op=in_trace_psum", 0)

        before = psum_count()

        from jax.sharding import PartitionSpec as P
        f = mesh_mod.compat_shard_map(
            lambda x: (coll.in_trace_psum(x, axis),
                       coll.in_trace_pmax(x, axis)),
            m, P(axis), (P(axis), P(axis)))
        x = jnp.arange(float(n)).reshape(n, 1)
        s, mx = f(x)
        np.testing.assert_allclose(
            np.asarray(s).ravel(), [x.sum()] * n)
        np.testing.assert_allclose(
            np.asarray(mx).ravel(), [x.max()] * n)
        assert psum_count() > before


# ---------------------------------------------------------------------------
# runtime host-sync sanitizer (ISSUE 11)
# ---------------------------------------------------------------------------

class TestHostSync:
    def _fresh(self):
        from paddle_tpu.analysis import host_sync
        return host_sync

    def test_in_step_sync_recorded_with_site(self):
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.profiler import RecordEvent
        hs = self._fresh()
        was = hs.installed()
        hs.install()
        hs.get_records().clear()
        try:
            x = jnp.ones((4,))
            np.asarray(x)                      # outside any span: silent
            assert hs.get_records().total == 0
            with RecordEvent("train_step"):
                np.asarray(x)                  # the blocking sync
            rep = hs.report()
            assert rep["in_step_syncs"] == 1
            assert rep["records"][0]["kind"] == "np.asarray"
            assert rep["records"][0]["span"] == "train_step"
            site = rep["records"][0]["site"]
            assert "test_static_analysis.py" in site and ":" in site
        finally:
            hs.get_records().clear()
            if not was:
                hs.uninstall()

    def test_block_until_ready_and_device_get(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.profiler import RecordEvent
        hs = self._fresh()
        was = hs.installed()
        hs.install()
        hs.get_records().clear()
        try:
            x = jnp.ones((2,))
            with RecordEvent("backward"):
                jax.block_until_ready(x)
                jax.device_get(x)
            kinds = {r["kind"] for r in hs.get_records().in_step()}
            assert kinds == {"block_until_ready", "device_get"}
        finally:
            hs.get_records().clear()
            if not was:
                hs.uninstall()

    def test_tensor_item_funnels_through(self):
        import jax.numpy as jnp
        from paddle_tpu.framework.tensor import Tensor
        from paddle_tpu.profiler import RecordEvent
        hs = self._fresh()
        was = hs.installed()
        hs.install()
        hs.get_records().clear()
        try:
            t = Tensor(jnp.ones(()), _internal=True)
            with RecordEvent("optimizer"):
                assert t.item() == 1.0
            assert hs.get_records().total == 1
        finally:
            hs.get_records().clear()
            if not was:
                hs.uninstall()

    def test_non_step_spans_and_plain_numpy_silent(self):
        import numpy as np
        from paddle_tpu.profiler import RecordEvent
        hs = self._fresh()
        was = hs.installed()
        hs.install()
        hs.get_records().clear()
        try:
            with RecordEvent("checkpoint"):    # host work by design
                np.asarray([1, 2, 3])
            with RecordEvent("train_step"):
                np.asarray([1, 2, 3])          # not a device array
            assert hs.get_records().total == 0
        finally:
            hs.get_records().clear()
            if not was:
                hs.uninstall()

    def test_uninstall_restores(self):
        import jax
        import numpy as np
        hs = self._fresh()
        if hs.installed():     # session-level install (flag run): skip
            pytest.skip("host-sync sanitizer active for the whole session")
        orig_asarray = np.asarray
        orig_block = jax.block_until_ready
        hs.install()
        assert np.asarray is not orig_asarray
        hs.uninstall()
        assert np.asarray is orig_asarray
        assert jax.block_until_ready is orig_block

    def test_flag_installs_sanitizer(self):
        import paddle_tpu
        hs = self._fresh()
        was = hs.installed()
        try:
            paddle_tpu.set_flags({"FLAGS_host_sync_check": True})
            assert hs.installed()
        finally:
            if not was:
                hs.uninstall()
            paddle_tpu.set_flags({"FLAGS_host_sync_check": was})

    def test_live_suite_is_clean(self):
        """Acceptance: under FLAGS_host_sync_check=1 the whole suite
        reports ZERO blocking syncs inside train-step spans. When the
        session runs with the flag, assert the live records; otherwise
        drive one real fused + one eager hapi train step under a local
        install and prove the same."""
        hs = self._fresh()
        if hs.installed():
            rep = hs.report()
            assert rep["in_step_syncs"] == 0, rep["sites"]
            return
        import numpy as np
        import paddle_tpu
        from paddle_tpu import hapi, nn, optimizer
        hs.install()
        hs.get_records().clear()
        try:
            net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
            model = hapi.Model(net)
            model.prepare(optimizer.SGD(learning_rate=0.1,
                                        parameters=net.parameters()),
                          nn.CrossEntropyLoss())
            x = paddle_tpu.to_tensor(
                np.random.RandomState(0).randn(8, 4).astype("float32"))
            y = paddle_tpu.to_tensor(
                np.zeros((8, 1), dtype="int64"))
            for _ in range(2):
                model.train_batch([x], [y])    # eager path spans
            rep = hs.report()
            assert rep["step_spans"] >= 4      # fwd/bwd/opt per step
            assert rep["in_step_syncs"] == 0, rep["sites"]
        finally:
            hs.get_records().clear()
            hs.uninstall()


# ---------------------------------------------------------------------------
# CFG construction + worklist solver (ISSUE 12 tentpole)
# ---------------------------------------------------------------------------

class TestCFG:
    def _cfg(self, src, name=None):
        import ast
        from paddle_tpu.analysis import dataflow
        tree = ast.parse(src)
        fns = [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        fn = fns[0] if name is None else \
            next(f for f in fns if f.name == name)
        return dataflow.build_cfg(fn)

    def _labels(self, cfg, idx_list):
        return [cfg.nodes[i].label for i in idx_list]

    def test_straight_line(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f():\n    a = 1\n    b = 2\n    return b\n")
        assert dataflow.CFG.EXIT in g.reachable_from(dataflow.CFG.ENTRY)
        # return has exactly one flow successor: EXIT
        ret = next(n for n in g.nodes if n.label == "return")
        assert ret.succs == [(dataflow.CFG.EXIT, "flow")]

    def test_if_else_branches_rejoin(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f(x):\n"
                      "    if x:\n"
                      "        a = 1\n"
                      "    else:\n"
                      "        a = 2\n"
                      "    return a\n")
        head = next(n for n in g.nodes if n.label == "if")
        flows = [d for d, k in head.succs if k == "flow"]
        assert len(flows) == 2             # both branches, no fallthrough

    def test_try_finally_return_in_finally(self):
        """return-in-finally swallows both the body's return and its
        exception: every path out of the function flows through the
        finally's own return node."""
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f():\n"
                      "    try:\n"
                      "        a = risky()\n"
                      "        return a\n"
                      "    finally:\n"
                      "        return 0\n")
        exit_preds = g.preds(dataflow.CFG.EXIT)
        fin_return = [i for i in exit_preds
                      if g.nodes[i].label == "return"
                      and g.nodes[i].line == 6]
        # the ONLY edges into EXIT come from the finally's return
        assert exit_preds == fin_return

    def test_while_else_and_break_skips_else(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f(xs):\n"
                      "    while xs:\n"
                      "        if bad(xs):\n"
                      "            break\n"
                      "        xs = step(xs)\n"
                      "    else:\n"
                      "        flag()\n"
                      "    return xs\n")
        brk = next(n for n in g.nodes if n.label == "break")
        ret = next(n for n in g.nodes if n.label == "return")
        els = next(n for n in g.nodes if n.line == 7)  # flag() in else
        # break jumps past the else, straight to the statement after
        assert (ret.idx, "flow") in brk.succs
        assert (els.idx, "flow") not in brk.succs
        # natural exhaustion runs the else
        head = next(n for n in g.nodes if n.label == "while")
        assert (els.idx, "flow") in head.succs

    def test_continue_targets_loop_head(self):
        g = self._cfg("def f(xs):\n"
                      "    for x in xs:\n"
                      "        if skip(x):\n"
                      "            continue\n"
                      "        use(x)\n")
        head = next(n for n in g.nodes if n.label == "for")
        cont = next(n for n in g.nodes if n.label == "continue")
        assert (head.idx, "flow") in cont.succs

    def test_while_true_has_no_natural_exit(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f():\n"
                      "    while True:\n"
                      "        if done():\n"
                      "            break\n"
                      "        step()\n"
                      "    return 1\n")
        head = next(n for n in g.nodes if n.label == "while")
        ret = next(n for n in g.nodes if n.label == "return")
        assert (ret.idx, "flow") not in head.succs   # only break reaches it
        brk = next(n for n in g.nodes if n.label == "break")
        assert (ret.idx, "flow") in brk.succs

    def test_nested_with_bodies_chain(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f(p):\n"
                      "    with open(p) as f:\n"
                      "        with lock:\n"
                      "            work(f)\n"
                      "    return 1\n")
        labels = [n.label for n in g.nodes]
        assert labels.count("with") == 2
        assert dataflow.CFG.EXIT in g.reachable_from(dataflow.CFG.ENTRY)

    def test_exception_edge_reaches_handler(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f():\n"
                      "    try:\n"
                      "        risky()\n"
                      "    except ValueError:\n"
                      "        recover()\n"
                      "    return 1\n")
        risky = next(n for n in g.nodes if n.line == 3)
        handler = next(n for n in g.nodes if n.label == "except")
        assert (handler.idx, "exc") in risky.succs
        # handler body rejoins normal flow at the return
        rec = next(n for n in g.nodes if n.line == 5)
        ret = next(n for n in g.nodes if n.label == "return")
        assert (ret.idx, "flow") in rec.succs

    def test_unprotected_statement_gets_panic_edge(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f():\n    risky()\n    return 1\n")
        risky = next(n for n in g.nodes if n.line == 2)
        assert (dataflow.CFG.EXIT, "panic") in risky.succs
        # ...and the panic edge is invisible to flow-only queries
        assert g.succs(risky.idx, dataflow.FLOW_ONLY) == \
            [n.idx for n in g.nodes if n.label == "return"]

    def test_generator_function_builds(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def gen(xs):\n"
                      "    for x in xs:\n"
                      "        yield x * 2\n"
                      "    yield -1\n")
        assert dataflow.CFG.EXIT in g.reachable_from(dataflow.CFG.ENTRY)
        head = next(n for n in g.nodes if n.label == "for")
        body = next(n for n in g.nodes if n.line == 3)
        assert (head.idx, "flow") in body.succs      # loop back edge

    def test_raise_routes_to_handler_not_exit(self):
        from paddle_tpu.analysis import dataflow
        g = self._cfg("def f():\n"
                      "    try:\n"
                      "        raise ValueError\n"
                      "    except ValueError:\n"
                      "        return 0\n")
        rse = next(n for n in g.nodes if n.label == "raise")
        handler = next(n for n in g.nodes if n.label == "except")
        assert rse.succs == [(handler.idx, "exc")]


class TestSolver:
    def _cfg(self, src):
        import ast
        from paddle_tpu.analysis import dataflow
        fn = ast.parse(src).body[0]
        return dataflow, dataflow.build_cfg(fn)

    def test_reaching_defs_merge_at_join(self):
        df, g = self._cfg("def f(c):\n"
                          "    x = 1\n"
                          "    if c:\n"
                          "        x = 2\n"
                          "    use(x)\n")
        rd = df.reaching_definitions(g)
        use = next(n for n in g.nodes if n.line == 5)
        defs = rd.defs_at(use.idx, "x")
        assert len(defs) == 2              # both assignments reach the use
        assert {g.nodes[d].line for d in defs} == {2, 4}

    def test_reaching_defs_kill(self):
        df, g = self._cfg("def f():\n"
                          "    x = 1\n"
                          "    x = 2\n"
                          "    use(x)\n")
        rd = df.reaching_definitions(g)
        use = next(n for n in g.nodes if n.line == 4)
        defs = rd.defs_at(use.idx, "x")
        assert [g.nodes[d].line for d in defs] == [3]

    def test_param_reaches_as_entry_def(self):
        df, g = self._cfg("def f(a):\n    use(a)\n")
        rd = df.reaching_definitions(g)
        use = next(n for n in g.nodes if n.line == 2)
        assert rd.defs_at(use.idx, "a") == [df.CFG.ENTRY]

    def test_liveness_backward(self):
        df, g = self._cfg("def f():\n"
                          "    x = 1\n"
                          "    y = 2\n"
                          "    return x\n")
        live = df.liveness(g)
        x_assign = next(n for n in g.nodes if n.line == 2)
        # after `x = 1`, x is live (read by return), y is not yet
        live_out = live[x_assign.idx][0]
        assert "x" in live_out

    def test_postdominators_flow_only(self):
        df, g = self._cfg("def f(c):\n"
                          "    a()\n"
                          "    if c:\n"
                          "        b()\n"
                          "    z()\n")
        pdom = df.postdominators(g)
        a = next(n for n in g.nodes if n.line == 2)
        b = next(n for n in g.nodes if n.line == 4)
        z = next(n for n in g.nodes if n.line == 5)
        assert z.idx in pdom[a.idx]        # z on every path after a
        assert b.idx not in pdom[a.idx]    # b only on the if-branch

    def test_intersect_meet_requires_universe(self):
        import pytest as _pytest
        df, g = self._cfg("def f():\n    pass\n")
        with _pytest.raises(ValueError):
            df.solve(g, direction="forward", transfer=lambda i, s: s,
                     meet="intersect")

    def test_convergence_bound_raises(self):
        import itertools
        import pytest as _pytest
        # needs a cycle: chaotic iteration on a DAG terminates even for
        # a non-monotone transfer
        df, g = self._cfg("def f(c):\n"
                          "    while c:\n"
                          "        a = step(a)\n")
        counter = itertools.count()

        def bad_transfer(idx, inset):       # never stabilizes
            return frozenset({next(counter)})

        with _pytest.raises(df.ConvergenceError):
            df.solve(g, direction="forward", transfer=bad_transfer,
                     max_iters=50)

    def test_repo_scale_solver_converges_on_every_function(self):
        """Satellite bound: CFG + reaching-defs + liveness converge for
        every function of all ~340 analyzed files (no ConvergenceError,
        no builder crash), and EXIT is reachable in every graph."""
        from paddle_tpu.analysis import dataflow
        findings, a = _repo_analysis()
        assert a.index is not None and a.dataflow is not None
        n_funcs = 0
        for fn in a.index.functions.values():
            g = a.dataflow.cfg(fn.node, fn.path)
            assert dataflow.CFG.EXIT in g.reachable_from(
                dataflow.CFG.ENTRY), fn.qualname
            a.dataflow.reaching(fn.node, fn.path)
            dataflow.liveness(g)
            n_funcs += 1
        assert n_funcs > 300               # repo scale, not a fixture


# ---------------------------------------------------------------------------
# F002 — future-await (ISSUE 12)
# ---------------------------------------------------------------------------

class TestFutureAwaitRule:
    def test_early_return_path_leaks_future(self):
        src = ("def f(b, bad):\n"
               "    fut = BucketFuture(b)\n"
               "    if bad:\n"
               "        return None\n"       # fut forgotten on this path
               "    return fut.wait()\n")
        f = _one(analyze_sources({"m.py": src}), "F002")
        assert "'fut'" in f.message and "path" in f.message

    def test_discarded_maker_call_flagged(self):
        src = "def f(b):\n    GatherFuture(b)\n"
        f = _one(analyze_sources({"m.py": src}), "F002")
        assert "discarded" in f.message

    def test_awaited_on_all_paths_ok(self):
        src = ("def f(b, bad):\n"
               "    fut = BucketFuture(b)\n"
               "    if bad:\n"
               "        return fut.result()\n"
               "    return fut.wait()\n")
        assert "F002" not in _rules(analyze_sources({"m.py": src}))

    def test_escape_via_store_ok(self):
        src = ("def f(self, b):\n"
               "    fut = BucketFuture(b)\n"
               "    self._futures[b.index] = fut\n")
        assert "F002" not in _rules(analyze_sources({"m.py": src}))

    def test_escape_via_return_ok(self):
        src = ("def f(b):\n"
               "    fut = GatherFuture(b)\n"
               "    return fut\n")
        assert "F002" not in _rules(analyze_sources({"m.py": src}))

    def test_drain_call_trusts_function(self):
        src = ("def f(self, b, bad):\n"
               "    fut = BucketFuture(b)\n"
               "    if bad:\n"
               "        self.abandon()\n"    # drains every lane future
               "        return None\n"
               "    return fut.wait()\n")
        assert "F002" not in _rules(analyze_sources({"m.py": src}))

    def test_sync_async_futures_list_tracked(self):
        src = ("def f(comm, params, bad):\n"
               "    futs = comm.sync_async(params)\n"
               "    if bad:\n"
               "        return None\n"
               "    for fu in futs:\n"
               "        fu.wait()\n")
        assert "F002" in _rules(analyze_sources({"m.py": src}))

    def test_repo_clean_on_f002(self):
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "F002"] == []


# ---------------------------------------------------------------------------
# F003 — manifest-last commit ordering (ISSUE 12)
# ---------------------------------------------------------------------------

_F003_GOOD = (
    "MANIFEST_NAME = 'MANIFEST.json'\n"
    "class M:\n"
    "    def attempt(self, entries, tmp):\n"
    "        for name, data in entries.items():\n"
    "            self._write_file(os.path.join(tmp, name), data)\n"
    "        self._write_file(os.path.join(tmp, MANIFEST_NAME), b'{}')\n"
    "        self.fs.replace(tmp, 'final')\n"
)

_F003_REORDERED = (
    "MANIFEST_NAME = 'MANIFEST.json'\n"
    "class M:\n"
    "    def attempt(self, entries, tmp):\n"
    "        self._write_file(os.path.join(tmp, MANIFEST_NAME), b'{}')\n"
    "        for name, data in entries.items():\n"
    "            self._write_file(os.path.join(tmp, name), data)\n"
    "        self.fs.replace(tmp, 'final')\n"
)


class TestCommitOrderRule:
    def test_manifest_last_proved(self):
        assert "F003" not in _rules(analyze_sources({"m.py": _F003_GOOD}))

    def test_reordered_write_flagged_with_path(self):
        """Acceptance (ISSUE 12): a deliberately reordered write is
        flagged with the violating path."""
        f = _one(analyze_sources({"m.py": _F003_REORDERED}), "F003")
        assert "post-dominated" in f.message and "path [" in f.message
        assert f.line == 6                 # the payload write

    def test_conditional_manifest_skip_flagged(self):
        src = (
            "MANIFEST_NAME = 'MANIFEST.json'\n"
            "def commit(entries, tmp, fast):\n"
            "    for name, data in entries.items():\n"
            "        _write_file(tmp + name, data)\n"
            "    if not fast:\n"
            "        _write_file(tmp + MANIFEST_NAME, b'{}')\n")
        assert "F003" in _rules(analyze_sources({"m.py": src}))

    def test_exception_abort_paths_exempt(self):
        # a raise between payload and manifest aborts the commit — the
        # checkpoint stays invisible, which is the protocol working
        src = (
            "MANIFEST_NAME = 'MANIFEST.json'\n"
            "def commit(entries, tmp):\n"
            "    for name, data in entries.items():\n"
            "        _write_file(tmp + name, data)\n"
            "    if torn(tmp):\n"
            "        raise OSError('torn')\n"
            "    _write_file(tmp + MANIFEST_NAME, b'{}')\n")
        assert "F003" not in _rules(analyze_sources({"m.py": src}))

    def test_payload_only_functions_out_of_scope(self):
        # save_shard's shape: payload writes, no manifest — rank 0
        # commits later; the cross-rank ordering is the barrier's job
        src = ("def save_shard(tmp, name, data):\n"
               "    _write_file(tmp + name, data)\n")
        assert "F003" not in _rules(analyze_sources({"m.py": src}))

    def test_live_commit_functions_statically_proved(self):
        """Acceptance (ISSUE 12): F003 proves manifest-last for every
        commit path in robustness/checkpoint.py — both commit closures
        were analyzed (not skipped) and came back clean."""
        findings, a = _repo_analysis()
        assert [f for f in findings if f.rule == "F003"] == []
        checker = next(c for c in a.checkers if c.name == "commit_order")
        proved = {(p, fn) for p, fn in checker.proved
                  if p == "paddle_tpu/robustness/checkpoint.py"}
        assert ("paddle_tpu/robustness/checkpoint.py", "attempt") in proved
        assert ("paddle_tpu/robustness/checkpoint.py", "commit") in proved


# ---------------------------------------------------------------------------
# X005 — mesh-axis validity (ISSUE 12)
# ---------------------------------------------------------------------------

_MESH_FIXTURE = (
    "AXIS_DATA = 'data'\n"
    "AXIS_MODEL = 'model'\n"
    "def build_mesh(topology):\n"
    "    pass\n"
)


class TestMeshAxisRule:
    def _run(self, user_src):
        return analyze_sources({
            "paddle_tpu/distributed/mesh.py": _MESH_FIXTURE,
            "paddle_tpu/user.py": user_src,
        })

    def test_literal_phantom_axis_flagged(self):
        src = ("import jax\n"
               "def f(x):\n"
               "    return jax.lax.psum(x, 'modle')\n")
        fs = [f for f in self._run(src) if f.rule == "X005"]
        assert len(fs) == 1 and "'modle'" in fs[0].message

    def test_known_axis_ok(self):
        src = ("import jax\n"
               "def f(x):\n"
               "    return jax.lax.psum(x, 'model')\n")
        assert "X005" not in _rules(self._run(src))

    def test_module_constant_resolves(self):
        src = ("import jax\n"
               "MY_AXIS = 'data'\n"
               "BAD_AXIS = 'bogus'\n"
               "def good(x):\n"
               "    return jax.lax.axis_index(MY_AXIS)\n"
               "def bad(x):\n"
               "    return jax.lax.axis_index(BAD_AXIS)\n")
        fs = [f for f in self._run(src) if f.rule == "X005"]
        assert len(fs) == 1 and "'bogus'" in fs[0].message

    def test_reaching_defs_resolve_local(self):
        src = ("import jax\n"
               "def f(x, cond):\n"
               "    ax = 'data'\n"
               "    if cond:\n"
               "        ax = 'ghost'\n"
               "    return jax.lax.psum(x, ax)\n")
        fs = [f for f in self._run(src) if f.rule == "X005"]
        assert len(fs) == 1 and "'ghost'" in fs[0].message

    def test_param_one_hop_through_callers(self):
        src = ("import jax\n"
               "def helper(x, axis):\n"
               "    return jax.lax.psum(x, axis)\n"
               "def caller(x):\n"
               "    return helper(x, 'phantom')\n")
        fs = [f for f in self._run(src) if f.rule == "X005"]
        assert len(fs) == 1 and "'phantom'" in fs[0].message

    def test_param_default_resolves(self):
        src = ("import jax\n"
               "def f(x, axis='model'):\n"
               "    return jax.lax.psum(x, axis)\n")
        assert "X005" not in _rules(self._run(src))

    def test_constrain_spec_tuple(self):
        src = ("BATCH = ('data', 'nope')\n"
               "def f(t):\n"
               "    return constrain(t, BATCH, None)\n")
        fs = [f for f in self._run(src) if f.rule == "X005"]
        assert len(fs) == 1 and "'nope'" in fs[0].message

    def test_shard_map_partition_spec(self):
        src = ("from jax.sharding import PartitionSpec as P\n"
               "def f(body, mesh, v):\n"
               "    spec = P('data', 'missing_ax')\n"
               "    fn = compat_shard_map(body, mesh, (spec,), spec)\n"
               "    return fn(v)\n")
        fs = [f for f in self._run(src) if f.rule == "X005"]
        assert fs and "'missing_ax'" in fs[0].message

    def test_build_mesh_topology_keys_register(self):
        src = ("import jax\n"
               "def setup():\n"
               "    return build_mesh({'expertish': 4})\n"
               "def f(x):\n"
               "    return jax.lax.psum(x, 'expertish')\n")
        assert "X005" not in _rules(self._run(src))

    def test_unresolvable_sites_skipped(self):
        src = ("import jax\n"
               "def f(x, axes):\n"
               "    return jax.lax.psum(x, axes[0])\n")
        assert "X005" not in _rules(self._run(src))

    def test_repo_zero_findings_with_real_coverage(self):
        """Acceptance (ISSUE 12): X005 validates every mesh-axis site in
        the live repo with zero false positives — and actually resolved a
        meaningful number of axes rather than skipping everything."""
        findings, a = _repo_analysis()
        assert [f for f in findings if f.rule == "X005"] == []
        checker = next(c for c in a.checkers if c.name == "mesh_axes")
        assert checker.stats["sites"] >= 40
        assert checker.stats["axes_validated"] >= 20

    def test_expert_axis_has_one_source_of_truth(self):
        """The live finding X005 surfaced: moe's 'expert' axis was a
        stringly-typed orphan; it now rides mesh.AXIS_EXPERT."""
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.distributed import moe
        assert moe.EXPERT_AXIS == mesh_mod.AXIS_EXPERT == "expert"


# ---------------------------------------------------------------------------
# check_static --fix (ISSUE 12 satellite) + per-rule timings
# ---------------------------------------------------------------------------

class TestCheckStaticFix:
    def _load_cli(self):
        spec = importlib.util.spec_from_file_location(
            "check_static", os.path.join(REPO, "tools", "check_static.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _write(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(
            "import threading\n"
            "t = threading.Thread(target=f)\n"
            "u = threading.Thread(\n"
            "    target=f,\n"
            "    name='w',\n"
            ")\n"
            "x = compute()  # lint-ok: C003 long gone\n")
        (tmp_path / "baseline.json").write_text('{"entries": []}\n')
        return mod

    def test_fix_dry_run_prints_diff_without_writing(self, tmp_path,
                                                     capsys):
        cli = self._load_cli()
        mod = self._write(tmp_path)
        before = mod.read_text()
        rc = cli.main(["--root", str(tmp_path), "--baseline",
                       str(tmp_path / "baseline.json"), "--no-cache",
                       "--fix"])
        out = capsys.readouterr().out
        assert rc == 0
        assert mod.read_text() == before          # dry run: untouched
        assert "+t = threading.Thread(target=f, daemon=True)" in out
        assert "lint-ok: C003" not in \
            [l for l in out.splitlines() if l.startswith("+")][-1]
        assert "dry run" in out

    def test_fix_apply_writes_and_run_is_clean(self, tmp_path, capsys):
        cli = self._load_cli()
        mod = self._write(tmp_path)
        rc = cli.main(["--root", str(tmp_path), "--baseline",
                       str(tmp_path / "baseline.json"), "--no-cache",
                       "--fix", "--apply"])
        assert rc == 0
        fixed = mod.read_text()
        assert fixed.count("daemon=True") == 2
        assert "lint-ok" not in fixed
        # the fixed tree parses and passes the gate
        rc = cli.main(["--root", str(tmp_path), "--baseline",
                       str(tmp_path / "baseline.json"), "--no-cache"])
        capsys.readouterr()
        assert rc == 0

    def test_json_reports_per_rule_timings(self, tmp_path, capsys):
        cli = self._load_cli()
        self._write(tmp_path)
        cli.main(["--root", str(tmp_path), "--baseline",
                  str(tmp_path / "baseline.json"), "--no-cache", "--json"])
        out = capsys.readouterr().out
        doc, _ = json.JSONDecoder().raw_decode(out.lstrip())
        timings = doc["rule_timings"]
        for name in ("index_build", "concurrency", "resource_release",
                     "commit_order", "mesh_axes"):
            assert name in timings
            assert isinstance(timings[name], float)

    def test_cfgs_persist_in_ast_cache(self, tmp_path):
        """Satellite: memoized CFGs ride the parsed-AST pickle — the
        second run rebuilds none of them."""
        from paddle_tpu.analysis import Analysis, AstCache, \
            default_checkers
        src_dir = tmp_path / "pkg"
        src_dir.mkdir()
        (src_dir / "m.py").write_text(
            "MANIFEST_NAME = 'MANIFEST.json'\n"
            "def commit(entries, tmp):\n"
            "    for name, data in entries.items():\n"
            "        _write_file(tmp + name, data)\n"
            "    _write_file(tmp + MANIFEST_NAME, b'{}')\n")
        cache_path = str(tmp_path / "cache.pkl")

        c1 = AstCache(cache_path)
        a1 = Analysis(default_checkers(), rel_root=str(tmp_path))
        assert a1.run_path(str(src_dir), cache=c1) == []
        assert a1.dataflow.built >= 1

        c2 = AstCache(cache_path)
        a2 = Analysis(default_checkers(), rel_root=str(tmp_path))
        assert a2.run_path(str(src_dir), cache=c2) == []
        assert a2.dataflow.built == 0
        assert a2.dataflow.from_cache >= 1


# ---------------------------------------------------------------------------
# future watch — the F002 runtime companion (ISSUE 12 satellite)
# ---------------------------------------------------------------------------

class TestFutureWatch:
    def test_counts_created_awaited_resolved(self):
        from paddle_tpu.analysis import host_sync as hs
        from paddle_tpu.distributed.overlap import BucketFuture
        from paddle_tpu.distributed.grad_comm import GradBucket
        import numpy as _np

        hs.install_future_watch()
        try:
            hs._future_counts.clear()
            b = GradBucket(0, _np.dtype("float32"))
            b.add(0, (1,))
            fut = BucketFuture(b, value=1.0, resolved=True)
            assert fut.wait() == 1.0
            fut2 = BucketFuture(b)
            fut2._resolve(2.0)
            rep = hs.future_report()
            c = rep["classes"]["BucketFuture"]
            assert c["created"] == 2
            assert c["awaited"] == 1           # fut2 never awaited
            assert c["resolved"] == 2
            assert rep["unawaited"] == 1
        finally:
            hs._future_counts.clear()
            hs.uninstall_future_watch()

    def test_direct_done_wait_counts_as_awaited(self):
        # the flush()/abandon()/free_bucket() drain path
        from paddle_tpu.analysis import host_sync as hs
        from paddle_tpu.distributed.overlap import GatherFuture
        from paddle_tpu.distributed.grad_comm import GradBucket
        import numpy as _np

        hs.install_future_watch()
        try:
            hs._future_counts.clear()
            b = GradBucket(1, _np.dtype("float32"))
            b.add(0, (1,))
            fut = GatherFuture(b)
            fut._resolve(3.0)
            fut._done.wait()
            rep = hs.future_report()
            c = rep["classes"]["GatherFuture"]
            assert c == {"created": 1, "awaited": 1, "resolved": 1}
        finally:
            hs._future_counts.clear()
            hs.uninstall_future_watch()

    def test_uninstall_restores_init(self):
        from paddle_tpu.analysis import host_sync as hs
        from paddle_tpu.distributed import overlap
        orig = overlap.BucketFuture.__init__
        hs.install_future_watch()
        assert overlap.BucketFuture.__init__ is not orig
        hs.uninstall_future_watch()
        assert overlap.BucketFuture.__init__ is orig


# ---------------------------------------------------------------------------
# F004 — drained requests re-admitted on every path (ISSUE 17)
# ---------------------------------------------------------------------------

class TestDrainReadmitRule:
    def test_early_return_path_leaks_drained_requests(self):
        src = ("def scale_down(self, bad):\n"
               "    drained = self.engine.drain()\n"
               "    if bad:\n"
               "        return None\n"       # drained forgotten here
               "    self.queue.requeue_front(drained)\n")
        f = _one(analyze_sources({"m.py": src}), "F004")
        assert "'drained'" in f.message and "path" in f.message
        assert f.line == 2                   # anchored at the drain()

    def test_discarded_drain_flagged(self):
        src = "def evict(self):\n    self.engine.drain()\n"
        f = _one(analyze_sources({"m.py": src}), "F004")
        assert "discarded" in f.message

    def test_readmitted_on_all_paths_ok(self):
        src = ("def scale_down(self, bad):\n"
               "    drained = self.engine.drain()\n"
               "    if bad:\n"
               "        self.queue.requeue_front(drained)\n"
               "        return None\n"
               "    self.queue.requeue_front(drained)\n")
        assert "F004" not in _rules(analyze_sources({"m.py": src}))

    def test_queue_close_retires_drained_ok(self):
        # shutdown: the requests are retired WITH the queue
        src = ("def stop(self):\n"
               "    drained = self.engine.drain()\n"
               "    self.queue.close()\n")
        assert "F004" not in _rules(analyze_sources({"m.py": src}))

    def test_return_transfers_ownership_ok(self):
        src = ("def fence(self):\n"
               "    drained = self.engine.drain()\n"
               "    return drained\n")
        assert "F004" not in _rules(analyze_sources({"m.py": src}))

    def test_store_to_attribute_escapes_ok(self):
        src = ("def fence(self):\n"
               "    drained = self.engine.drain()\n"
               "    self._pending = drained\n")
        assert "F004" not in _rules(analyze_sources({"m.py": src}))

    def test_exception_between_drain_and_requeue_leaks(self):
        # a raise-capable call between fence and re-admission: the
        # NO_PANIC path set still sees the early `return` leak below
        src = ("def scale_down(self, idx):\n"
               "    drained = self.engine.drain()\n"
               "    if not drained:\n"
               "        return 0\n"
               "    self.hd.stop()\n"
               "    self.queue.requeue_front(drained)\n"
               "    return len(drained)\n")
        # empty-list early return still carries the (empty) obligation —
        # the rule is syntactic about ownership, not list length; the
        # idiom is to requeue unconditionally (it is a no-op when empty)
        assert "F004" in _rules(analyze_sources({"m.py": src}))

    def test_unrelated_drain_like_names_out_of_scope(self):
        # drain(x) with args, or a bare-name drain() call, is not the
        # engine-fence maker
        src = ("def f(tank):\n"
               "    drain(tank)\n"
               "    water = drain()\n")
        assert "F004" not in _rules(analyze_sources({"m.py": src}))

    def test_live_scale_and_evict_paths_statically_proved(self):
        """Acceptance (ISSUE 17): every drain() in the serving runtime —
        evict(), scale_down(), and the fleet harness — is proved paired
        with re-admission or queue retirement on all non-panic paths."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "F004"] == []


class TestSpanCloseRule:
    """F005 (ISSUE 18): begin_span() obligations close on ALL paths —
    exception edges included, like F001. The proof shape is bind-None,
    open inside try, end_span in finally (what tracing.span() does)."""

    def test_early_return_path_leaks_span(self):
        src = ("def prefill(self, ctx, bad):\n"
               "    sp = self.tracer.begin_span(ctx, 'prefill')\n"
               "    if bad:\n"
               "        return None\n"        # sp never ended here
               "    self.tracer.end_span(sp)\n")
        f = _one(analyze_sources({"m.py": src}), "F005")
        assert "'sp'" in f.message and "path" in f.message
        assert f.line == 2                    # anchored at the open

    def test_exception_edge_leaks_without_finally(self):
        # a straight-line close is NOT enough: work() can raise, and the
        # exception edge reaches exit before end_span — F005 runs with
        # ALL_KINDS, so only a finally (or the span() cm) discharges it
        src = ("def decode(self, ctx):\n"
               "    sp = self.tracer.begin_span(ctx, 'decode')\n"
               "    self.work()\n"
               "    self.tracer.end_span(sp)\n")
        assert "F005" in _rules(analyze_sources({"m.py": src}))

    def test_try_finally_close_proved(self):
        src = ("def decode(self, ctx):\n"
               "    sp = None\n"
               "    try:\n"
               "        sp = self.tracer.begin_span(ctx, 'decode')\n"
               "        self.work()\n"
               "    finally:\n"
               "        self.tracer.end_span(sp)\n")
        assert "F005" not in _rules(analyze_sources({"m.py": src}))

    def test_span_contextmanager_shape_proved(self):
        # the generator behind `with tracer.span(...)`: yield escapes to
        # the caller AND the finally ends it — clean on every edge
        src = ("def span(self, ctx, name):\n"
               "    sp = None\n"
               "    try:\n"
               "        sp = self.begin_span(ctx, name)\n"
               "        yield sp\n"
               "    finally:\n"
               "        self.end_span(sp)\n")
        assert "F005" not in _rules(analyze_sources({"m.py": src}))

    def test_discarded_begin_span_flagged(self):
        src = "def f(self, ctx):\n    self.tracer.begin_span(ctx, 'x')\n"
        f = _one(analyze_sources({"m.py": src}), "F005")
        assert "discarded" in f.message

    def test_direct_return_out_of_scope_ok(self):
        # never bound to a local: the caller owns the close
        src = ("def open_hop(self, ctx):\n"
               "    return self.tracer.begin_span(ctx, 'hop')\n")
        assert "F005" not in _rules(analyze_sources({"m.py": src}))

    def test_direct_attribute_store_ok(self):
        # escapes to an object that outlives the frame and closes later
        src = ("def arm(self, ctx):\n"
               "    self._sp = self.tracer.begin_span(ctx, 'bg')\n")
        assert "F005" not in _rules(analyze_sources({"m.py": src}))

    def test_waiver_suppresses(self):
        src = ("def f(self, ctx):\n"
               "    sp = self.tracer.begin_span(ctx, 'x')"
               "  # lint-ok: F005 closed by callee\n"
               "    self.stash(sp)\n")
        assert "F005" not in _rules(analyze_sources({"m.py": src}))

    def test_record_span_out_of_scope(self):
        # one-shot spans open nothing — the preferred lifecycle-edge API
        src = ("def retire(self, ctx):\n"
               "    self.tracer.record_span(ctx, 'retire', outcome='ok')\n")
        assert "F005" not in _rules(analyze_sources({"m.py": src}))

    def test_live_tracing_span_statically_proved(self):
        """Acceptance (ISSUE 18): every begin_span site in the repo —
        including tracing.span() itself — closes on all paths."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "F005"] == []


# ---------------------------------------------------------------------------
# F006 — standby promoted or torn down on every path (ISSUE 19)
# ---------------------------------------------------------------------------

class TestStandbyLifecycleRule:
    """F006: a standby acquired for warm handoff (``acquire_standby()``)
    must be promoted into the set OR torn down on every non-panic CFG
    path — a leaked standby is a live engine + KV pool no watchdog
    fences. NO_PANIC like F002/F004: cleanup code is trusted, and the
    idiomatic discharge is unconditional per branch (a conditional
    discharge in a ``finally`` creates infeasible-path false
    positives)."""

    def test_leaked_on_timeout_branch_flagged(self):
        src = ("def scale_up(self, warm):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    if not sb.ready():\n"
               "        return None\n"           # timeout branch leaks sb
               "    return sb.promote()\n")
        f = _one(analyze_sources({"m.py": src}), "F006")
        assert "'sb'" in f.message and "neither promoted nor torn down" \
            in f.message
        assert f.line == 2                       # anchored at the acquire

    def test_discarded_acquire_flagged(self):
        src = ("def grow(self):\n"
               "    self.rset.acquire_standby()\n")
        f = _one(analyze_sources({"m.py": src}), "F006")
        assert "discarded" in f.message

    def test_promote_or_abandon_per_branch_proved(self):
        # the live scale_up shape: unexpected exceptions abandon+raise,
        # then each post-try branch discharges unconditionally
        src = ("def scale_up(self):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    ok = False\n"
               "    try:\n"
               "        sb.warm(self.buckets())\n"
               "        ok = sb.ready()\n"
               "    except TimeoutError:\n"
               "        ok = False\n"
               "    except BaseException:\n"
               "        sb.abandon()\n"
               "        raise\n"
               "    if ok:\n"
               "        return sb.promote()\n"
               "    sb.abandon()\n"
               "    return None\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_panic_edges_trusted_by_design(self):
        # NO_PANIC semantics: the implicit may-raise edge of sb.warm()
        # is NOT tracked (the maker's own panic edge would otherwise
        # make every fixture unprovable). The repo's discipline for
        # unexpected exceptions is the explicit `except BaseException:
        # abandon(); raise` branch, proved by the per-branch fixture.
        src = ("def scale_up(self):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    sb.warm(self.buckets())\n"
               "    return sb.promote()\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_swap_in_arg_form_discharges(self):
        src = ("def grow(self):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    self.rset.swap_in(sb)\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_return_transfers_ownership(self):
        src = ("def make_standby(self):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    return sb\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_attribute_store_escapes(self):
        src = ("def park(self):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    self._parked = sb\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_stop_alias_discharges(self):
        src = ("def probe(self):\n"
               "    sb = self.rset.acquire_standby()\n"
               "    sb.stop()\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_waiver_suppresses(self):
        src = ("def grow(self):\n"
               "    self.rset.acquire_standby()"
               "  # lint-ok: F006 adopted by callee\n")
        assert "F006" not in _rules(analyze_sources({"m.py": src}))

    def test_live_warm_handoff_paths_statically_proved(self):
        """Acceptance (ISSUE 19): every acquire_standby in the repo —
        scale_up(warm=True) with its boot-budget timeout and exception
        branches — discharges the standby on all non-panic paths."""
        findings, _ = _repo_analysis()
        assert [f for f in findings if f.rule == "F006"] == []
