"""dlint self-tests: every analyzer must fire on its seeded-violation
fixture (right rule id, right line), the live repo must scan clean, and
a suppression comment must suppress exactly one finding.

All fixture trees are built under tmp_path with the repo's layout
(``dllama_tpu/...``); no jax anywhere — the lint must run on bare CI
runners."""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap
from types import SimpleNamespace

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.dlint import Project, all_rules, get_rule  # noqa: E402
from tools.dlint.core import run_rule  # noqa: E402


def _tree(tmp_path, files: dict[str, str]) -> Project:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return Project(tmp_path)


def _run(name: str, project: Project):
    return run_rule(get_rule(name), project)


# -- framework ----------------------------------------------------------------

def test_all_rules_registered():
    names = set(all_rules())
    assert {"jit-entry", "shard-map-shim", "tracer-hazard", "guarded-twin",
            "thread-ownership", "lock-guard", "lock-order",
            "metrics-names", "exception-hygiene", "route-labels",
            "failpoint-sites", "span-phases", "pallas-gate",
            "tenant-reasons"} <= names


def test_live_repo_scans_clean():
    """The acceptance bar: python -m tools.dlint exits 0 on the repo."""
    from tools.dlint.core import run_rules

    rc = run_rules(Project(REPO), stream=open("/dev/null", "w"))
    assert rc == 0


def test_json_summary_cli():
    out = subprocess.run(
        [sys.executable, "-m", "tools.dlint", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ok"] is True
    assert payload["findings"] == 0
    assert payload["rules"] >= 12


def test_unknown_rule_is_an_error():
    out = subprocess.run(
        [sys.executable, "-m", "tools.dlint", "--only", "no-such-rule"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert "unknown rule" in out.stderr


# -- trace safety -------------------------------------------------------------

_TRACED_FIXTURE = {
    "dllama_tpu/models/bad.py": """\
        import time
        import numpy as np


        def my_sampled_step(params, cfg, x, kv):
            t = time.time()
            if x > 0:
                y = bool(x)
            z = x.item()
            r = np.random.rand()
            return x
        """,
    "dllama_tpu/runtime/engine.py": """\
        from ..models.bad import my_sampled_step


        def build(engine):
            return plan_scoped_jit(my_sampled_step, static_argnums=1)
        """,
}


def test_tracer_hazards_fire_with_rule_and_line(tmp_path):
    project = _tree(tmp_path, _TRACED_FIXTURE)
    res = _run("tracer-hazard", project)
    got = {(f.rule, f.lineno) for f in res.findings}
    assert ("tracer-ambient", 6) in got      # time.time()
    assert ("tracer-branch", 7) in got       # if x > 0
    assert ("tracer-host-sync", 8) in got    # bool(x)
    assert ("tracer-host-sync", 9) in got    # .item()
    assert ("tracer-ambient", 10) in got     # np.random.rand()
    assert all(f.path == "dllama_tpu/models/bad.py" for f in res.findings)


def test_suppression_suppresses_exactly_one_finding(tmp_path):
    files = dict(_TRACED_FIXTURE)
    files["dllama_tpu/models/bad.py"] = files[
        "dllama_tpu/models/bad.py"].replace(
        "t = time.time()",
        "t = time.time()  # dlint: disable=tracer-ambient")
    project = _tree(tmp_path, files)
    res = _run("tracer-hazard", project)
    assert len(res.suppressed) == 1
    assert res.suppressed[0].rule == "tracer-ambient"
    assert res.suppressed[0].lineno == 6
    # the other findings (including the OTHER tracer-ambient) still fire
    got = {(f.rule, f.lineno) for f in res.findings}
    assert ("tracer-ambient", 10) in got
    assert ("tracer-host-sync", 9) in got


def test_raw_jit_fires_and_static_gates_untaint(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/models/rawjit.py": """\
            import jax


            def g(x):
                return x


            h = jax.jit(g)
            """,
        "dllama_tpu/ops/gates.py": """\
            def is_fast(x):  # dlint: static-fn
                return str(x.dtype) == "bfloat16"


            def op(params, cfg, x):
                fast = is_fast(x)
                if fast:
                    return x
                return x + 1
            """,
        "dllama_tpu/runtime/wire.py": """\
            from ..ops.gates import op


            def build():
                return plan_scoped_jit(op)
            """,
    })
    res = _run("jit-entry", project)
    assert [(f.rule, f.path, f.lineno) for f in res.findings] == [
        ("jit-entry", "dllama_tpu/models/rawjit.py", 8)]
    # the declared static-fn gate keeps `if fast:` out of tracer-branch
    res = _run("tracer-hazard", project)
    assert res.findings == []


def test_shard_map_shim_fires_on_code_not_prose(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/parallel/qc.py": '''\
            """Docs may name jax.experimental.shard_map freely."""
            # a comment naming jax.shard_map is fine too
            from jax.experimental.shard_map import shard_map
            ''',
    })
    res = _run("shard-map-shim", project)
    assert [(f.path, f.lineno) for f in res.findings] == [
        ("dllama_tpu/parallel/qc.py", 3)]


def test_guarded_twin_completeness(tmp_path):
    """A decode program WITHOUT the tripwire is the finding: one that does
    not end in ``_guarded``, one that ends so and takes no ``poison``, and a
    replicated one alike. The survivors (a ``_guarded`` program that takes
    ``poison``), ``forward``-named programs and private helpers are not."""
    project = _tree(tmp_path, {
        "dllama_tpu/models/llama.py": """\
            def fancy_sampled_step(params, cfg, tokens, pos, kv):
                return tokens


            def sampled_step_guarded(params, cfg, tokens, pos, kv, poison):
                return tokens


            def greedy_steps_guarded(params, cfg, token, pos, kv, n_steps):
                return token


            def forward_and_step(params, cfg, tokens, pos, kv):
                return tokens


            def _scan_decode_guarded(step1, token, pos, kv, n_steps):
                return token
            """,
        "dllama_tpu/parallel/multihost.py": """\
            def replicated_forward(params, cfg, tokens, pos, kv):
                return tokens


            def replicated(program):
                return program


            def replicated_greedy(params, cfg, tokens, pos, kv):
                return tokens
            """,
    })
    res = _run("guarded-twin", project)
    assert [(f.rule, f.path, f.lineno) for f in res.findings] == [
        ("guarded-twin", "dllama_tpu/models/llama.py", 1),
        ("guarded-twin", "dllama_tpu/models/llama.py", 9),
        ("guarded-twin", "dllama_tpu/parallel/multihost.py", 9)]
    assert "fancy_sampled_step" in res.findings[0].message
    assert "greedy_steps_guarded" in res.findings[1].message
    assert "replicated_greedy" in res.findings[2].message


# -- thread ownership ---------------------------------------------------------

def test_monitor_path_reaching_loop_owned_mutator_fires(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/kvblocks.py": """\
            class BlockPool:
                def alloc(self):  # dlint: owner=loop-thread
                    return 1
            """,
        "dllama_tpu/runtime/serving.py": """\
            class Sched:
                def _on_stall(self, info):  # dlint: owner=monitor-thread
                    self._cleanup()

                def _cleanup(self):
                    self.pool.alloc()

                def _on_crash(self, exc):  # dlint: owner=loop-thread
                    pass

                def _fail_all(self, msg):  # dlint: owner=any
                    pass
            """,
        "dllama_tpu/runtime/watchdog.py": "",
    })
    res = _run("thread-ownership", project)
    assert len(res.findings) == 1
    f = res.findings[0]
    assert f.lineno == 2 and "BlockPool.alloc" in f.message \
        and "_cleanup" in f.message


def test_missing_supervision_annotation_fires(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/serving.py": """\
            class Sched:
                def helper(self):  # dlint: owner=any
                    pass

                def _on_stall(self, info):
                    pass
            """,
        "dllama_tpu/runtime/kvblocks.py": "",
        "dllama_tpu/runtime/watchdog.py": "",
    })
    res = _run("thread-ownership", project)
    assert [f.lineno for f in res.findings] == [5]
    assert "owner=" in res.findings[0].message


def test_unguarded_shared_state_write_fires(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/serving.py": """\
            import threading


            class Sched:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._queue = []  # dlint: guarded-by=_lock

                def good(self, req):
                    with self._lock:
                        self._queue.append(req)

                def bad(self, req):
                    self._queue.append(req)
                    self._queue = []
            """,
        "dllama_tpu/runtime/kvblocks.py": "",
        "dllama_tpu/runtime/watchdog.py": "",
    })
    res = _run("lock-guard", project)
    assert [f.lineno for f in res.findings] == [14, 15]
    assert all("_queue" in f.message for f in res.findings)


def test_lock_order_cycle_fires(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/locky.py": """\
            import threading


            class Alpha:
                def __init__(self):
                    self._lock = threading.Lock()

                def hold_alpha(self):
                    with self._lock:
                        cross_to_beta()

                def take_alpha(self):
                    with self._lock:
                        pass


            class Beta:
                def __init__(self):
                    self._lock = threading.Lock()

                def hold_beta(self):
                    with self._lock:
                        cross_to_alpha()


            def cross_to_beta():
                Beta().hold_beta()


            def cross_to_alpha():
                Alpha().take_alpha()
            """,
    })
    res = _run("lock-order", project)
    assert any("cycle" in f.message for f in res.findings)
    msg = next(f.message for f in res.findings if "cycle" in f.message)
    assert "Alpha._lock" in msg and "Beta._lock" in msg


def test_lock_self_deadlock_fires(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/locky.py": """\
            import threading


            class Gamma:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """,
    })
    res = _run("lock-order", project)
    assert any("self-deadlock" in f.message for f in res.findings)


# -- the six migrated rules fire on seeded fixtures ---------------------------

def test_metrics_names_fixture_violations(tmp_path):
    from tools.dlint import metrics_names

    project = _tree(tmp_path, {
        "dllama_tpu/runtime/TELEMETRY.md": "dllama_counter_total\n",
        "dllama_tpu/x.py": 'NAME = "dllama_orphan_total"\n',
    })
    specs = {
        "dllama_counter": SimpleNamespace(kind="counter", help="x"),
        "dllama_Bad": SimpleNamespace(kind="gauge", help="y"),
    }
    findings, _ = metrics_names.check(project, specs=specs)
    msgs = "\n".join(f.message for f in findings)
    assert "must end in _total" in msgs
    assert "violates" in msgs                       # dllama_Bad naming
    assert "dllama_orphan_total" in msgs            # unregistered literal


def test_exception_hygiene_fixture_violations(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/bad.py": """\
            def f():
                try:
                    pass
                except:
                    pass


            def g():
                try:
                    pass
                except Exception:
                    return None
            """,
    })
    res = _run("exception-hygiene", project)
    assert [f.lineno for f in res.findings] == [4, 11]
    assert "bare" in res.findings[0].message
    assert "BLE001" in res.findings[1].message


def test_route_labels_fixture_violation(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/serve/api.py": """\
            _ROUTES = ("/v1/x", "/debug")
            _DEBUG_INDEX = {}


            class H:
                def do(self):
                    path = "/v1/x"
                    if path == "/v1/unregistered":
                        pass
            """,
    })
    res = _run("route-labels", project)
    assert any("/v1/unregistered" in f.message and f.lineno == 8
               for f in res.findings)


def test_failpoint_sites_fixture_violations(tmp_path):
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/failpoints.py": '''\
            """Registry.

            * ``site_a`` — documented but never fired
            """
            ''',
        "dllama_tpu/runtime/uses.py": """\
            from . import failpoints


            def f():
                failpoints.fire("site_b")
            """,
    })
    res = _run("failpoint-sites", project)
    msgs = "\n".join(f.message for f in res.findings)
    assert "site_b" in msgs and "not documented" in msgs
    assert "site_a" in msgs and "never fired" in msgs


def test_span_phases_fixture_violation(tmp_path):
    from tools.dlint import span_phases

    project = _tree(tmp_path, {
        "dllama_tpu/runtime/emits.py": """\
            from . import telemetry


            def f(rid, t0, t1):
                telemetry.tracer().emit(rid, "bogus_phase", t0, t1)
            """,
    })
    findings, _ = span_phases.check(project, phases=(("queue",), ()))
    msgs = "\n".join(f.message for f in findings)
    assert "bogus_phase" in msgs                    # emitted, not in PHASES
    assert "queue" in msgs                          # documented, never emitted


def test_span_phases_router_vocabulary(tmp_path):
    """RouterSpanRing.emit_span literals are held to ROUTER_PHASES the
    same way tracer().emit literals are held to PHASES."""
    from tools.dlint import span_phases

    project = _tree(tmp_path, {
        "dllama_tpu/serve/rt.py": """\
            def f(spans, rid, t0, t1):
                spans.emit_span(rid, "rt_bogus", t0, t1)
            """,
    })
    findings, _ = span_phases.check(
        project, phases=((), ("rt_queue",)))
    msgs = "\n".join(f.message for f in findings)
    assert "rt_bogus" in msgs                   # emitted, not in vocabulary
    assert "rt_queue" in msgs                   # documented, never emitted


_TICK_FIXTURE = {
    "dllama_tpu/runtime/loop.py": """\
        def tick(flight):
            with flight.tick_phase("emit") as ph:
                ph.next_phase("bogus_wait")
            with flight.tick_phase("not_a_phase"):
                pass
        """,
    "dllama_tpu/runtime/telemetry.py": """\
        # * ``emit`` — documented here
        # * ``never_used`` — documented, no call site
        TICK_PHASES = ("emit", "never_used", "undocumented")
        """,
    "dllama_tpu/runtime/TELEMETRY.md": "emit never_used\n",
}


def _tick_findings(tmp_path, vocab):
    from tools.dlint import span_phases

    findings, _ = span_phases.check(_tree(tmp_path, _TICK_FIXTURE),
                                    phases=((), (), vocab))
    return "\n".join(f.message for f in findings)


def test_span_phases_tick_literal_outside_vocabulary(tmp_path):
    """tick_phase / next_phase literals are held to TICK_PHASES the way
    tracer().emit literals are held to PHASES."""
    msgs = _tick_findings(tmp_path, ("emit", "never_used", "undocumented"))
    assert "'not_a_phase' which is not in telemetry.TICK_PHASES" in msgs
    assert "'bogus_wait' which is not in telemetry.TICK_PHASES" in msgs
    assert "'emit' which is not in" not in msgs


def test_span_phases_unused_tick_phase(tmp_path):
    msgs = _tick_findings(tmp_path, ("emit", "never_used", "undocumented"))
    assert "telemetry.TICK_PHASES documents 'never_used' but no call site" in msgs
    assert "documents 'emit' but no call site" not in msgs


def test_span_phases_undocumented_tick_phase(tmp_path):
    msgs = _tick_findings(tmp_path, ("emit", "never_used", "undocumented"))
    assert "'undocumented' is not documented in TELEMETRY.md" in msgs
    assert "'undocumented' is not described in the telemetry.py" in msgs
    assert "'emit' is not documented" not in msgs


def test_span_phases_tick_phase_must_be_a_literal(tmp_path):
    from tools.dlint import span_phases

    project = _tree(tmp_path, {
        "dllama_tpu/runtime/loop.py": """\
            def tick(flight, name):
                with flight.tick_phase(name):
                    pass
            """,
    })
    findings, _ = span_phases.check(project, phases=((), (), ("emit",)))
    assert any("tick_phase phase argument is not a string constant" in f.message
               and f.lineno == 2 for f in findings)


_STALL_FIXTURE = {
    "dllama_tpu/runtime/rec.py": """\
        from . import telemetry


        def stall_cause(where, cpu):
            if cpu > 1:
                return "own_code"
            if cpu < 0:
                return where
            return "bogus_cause" if where else "unknown"


        def gap(counter, ms):
            counter.inc(ms, phase=telemetry.BETWEEN_TICKS)
        """,
    "dllama_tpu/runtime/telemetry.py": """\
        # * ``between_ticks`` and ``between_phases`` - documented here
        # * ``own_code`` / ``unknown`` / ``never_returned`` - documented here
        BETWEEN_TICKS = "between_ticks"
        BETWEEN_PHASES = "between_phases"
        BETWEEN_NOTHING = "undocumented_gap"
        """,
    "dllama_tpu/runtime/TELEMETRY.md": "between_ticks between_phases own_code unknown never_returned\n",
}


def test_span_phases_loop_gaps_and_stall_causes_are_closed(tmp_path):
    """LOOP_GAPS: emitted where another module reads the constant off
    telemetry. STALL_CAUSES: emitted where ``stall_cause`` returns the
    literal, every arm of a conditional return included."""
    from tools.dlint import span_phases

    findings, summary = span_phases.check(
        _tree(tmp_path, _STALL_FIXTURE),
        phases=((), (), (), ("between_ticks", "between_phases", "undocumented_gap"),
                ("own_code", "unknown", "never_returned")))
    msgs = "\n".join(f.message for f in findings)
    assert "telemetry.LOOP_GAPS documents 'between_phases' but no call site" in msgs
    assert "documents 'between_ticks' but no call site" not in msgs
    assert "'undocumented_gap' is not documented in TELEMETRY.md" in msgs
    assert "'bogus_cause' which is not in telemetry.STALL_CAUSES" in msgs
    assert "telemetry.STALL_CAUSES documents 'never_returned' but no call site" in msgs
    assert "documents 'unknown' but no call site" not in msgs
    assert any("stall_cause returns something that is not a string constant" in f.message
               and f.lineno == 8 for f in findings)
    assert "3 loop gaps + 3 stall causes" in summary


def test_span_phases_live_tick_vocabulary_is_closed():
    """The live tree: every TICK_PHASES name has a call site in
    runtime/serving.py and the two-tuple form older fixtures pass still
    means 'no tick vocabulary'."""
    from tools.dlint import span_phases

    findings, summary = span_phases.check(Project(REPO))
    assert findings == []
    assert "12 tick phases + 2 loop gaps + 7 stall causes" in summary
    findings, _ = span_phases.check(Project(REPO), phases=((), ()))
    assert all("TICK_PHASES documents" not in f.message for f in findings)


def test_pallas_gate_fixture_violation(tmp_path):
    """A new kernel module dispatching pl.pallas_call without consulting
    quant_matmul.pallas_mode_gate fires pallas-gate at the call line; a
    module that routes through the gate — and the exempt legacy modules —
    stay clean."""
    project = _tree(tmp_path, {
        "dllama_tpu/ops/rogue_kernel.py": """\
            from jax.experimental import pallas as pl


            def _kernel(x_ref, o_ref):
                o_ref[:] = x_ref[:]


            def rogue(x):
                import os
                interpret = os.environ.get("MY_OWN_KNOB") == "1"
                return pl.pallas_call(_kernel, out_shape=None,
                                      interpret=interpret)(x)
            """,
        "dllama_tpu/ops/good_kernel.py": """\
            from jax.experimental import pallas as pl

            from .quant_matmul import pallas_mode_gate


            def _kernel(x_ref, o_ref):
                o_ref[:] = x_ref[:]


            def good(x):
                kw = pallas_mode_gate(False)
                if kw is None:
                    return None
                return pl.pallas_call(_kernel, out_shape=None, **kw)(x)
            """,
        "dllama_tpu/ops/sneaky_kernel.py": """\
            from jax.experimental import pallas as pl

            from .quant_matmul import pallas_mode_gate  # imported, never CALLED


            def sneaky(x):
                return pl.pallas_call(lambda i, o: None, out_shape=None)(x)
            """,
        "dllama_tpu/ops/quant_matmul.py": """\
            from jax.experimental import pallas as pl


            def pallas_mode_gate(fast):
                return {"interpret": True}


            def run(x):
                return pl.pallas_call(lambda i, o: None, out_shape=None)(x)
            """,
    })
    res = _run("pallas-gate", project)
    assert len(res.findings) == 2, [str(f) for f in res.findings]
    by_path = {f.path.rsplit("/", 1)[-1]: f for f in res.findings}
    # a module with its own env knob fires; so does one that merely
    # IMPORTS the gate without calling it (an unused import is not a
    # consult)
    assert set(by_path) == {"rogue_kernel.py", "sneaky_kernel.py"}
    f = by_path["rogue_kernel.py"]
    assert "pallas_mode_gate" in f.message
    # the finding anchors the pallas_call line itself
    src = (tmp_path / "dllama_tpu/ops/rogue_kernel.py").read_text()
    assert "pl.pallas_call" in src.splitlines()[f.lineno - 1]


def test_pallas_gate_live_repo_kernels_routed():
    """The real kernel modules: paged_attention (and any future kernel
    module) must consult the shared gate; the two legacy modules are the
    documented exempt list."""
    res = _run("pallas-gate", Project(REPO))
    assert not res.findings, [str(f) for f in res.findings]


def test_shard_map_rule_runs_from_cli():
    """One rule by name through the module's own command line."""
    out = subprocess.run(
        [sys.executable, "-m", "tools.dlint", "--only", "shard-map-shim"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "shard-map-shim" in out.stdout


# -- tenant-reasons ------------------------------------------------------------

def test_tenant_reasons_fixture(tmp_path):
    """Both closed-world directions on a seeded fixture: an emit site
    naming an undeclared reason fires, and a declared reason with no
    emit site fires (injectable vocabulary, no repo import)."""
    from tools.dlint import tenant_names

    proj = _tree(tmp_path, {
        "dllama_tpu/runtime/tenancy.py": '''
            # * ``queue_full`` — the shared bound shed the submit.
            # * ``ghost_reason`` — declared but never emitted.
            ADMIT_REASONS = ("queue_full", "ghost_reason")
        ''',
        "dllama_tpu/runtime/serving.py": '''
            class S:
                def submit(self, tenant):
                    self._tenancy.note_shed(tenant, "queue_full")
                    self.flight.note("shed", reason="queue_full",
                                     tenant=tenant)
                    self.flight.note("defer", rid,
                                     reason="mystery_reason",
                                     tenant=tenant)
                    # lifecycle reasons are out of scope for the rule
                    self.flight.note("timeout", rid, reason="queued",
                                     tenant=tenant)
        ''',
        "dllama_tpu/serve/router.py": "",
        "dllama_tpu/runtime/TELEMETRY.md": "`dllama_tenant_shed_total{tenant,reason}` — sheds.\n"
                   "Reasons: queue_full, ghost_reason.\n",
    })
    specs = {"dllama_tenant_shed_total": SimpleNamespace(
        kind="counter", help="sheds")}
    findings, _ = tenant_names.check(
        proj, vocab=(("queue_full", "ghost_reason"), specs))
    msgs = [f.message for f in findings]
    assert any("mystery_reason" in m and "not in tenancy.ADMIT_REASONS"
               in m for m in msgs), msgs
    assert any("ghost_reason" in m and "no emit site" in m
               for m in msgs), msgs
    # nothing else fires: the in-scope emit sites are vocabulary-clean,
    # the docs cover the metric family and both declared reasons
    assert len(findings) == 2, msgs
    assert all(f.rule == "tenant-reasons" for f in findings)
    # the finding anchors the offending emit line
    bad = next(f for f in findings if "mystery_reason" in f.message)
    src = (tmp_path / "dllama_tpu/runtime/serving.py").read_text()
    assert 'reason="mystery_reason"' in "".join(
        src.splitlines()[bad.lineno - 1:bad.lineno + 1])


def test_tenant_reasons_live_repo_clean():
    res = _run("tenant-reasons", Project(REPO))
    assert not res.findings, [str(f) for f in res.findings]


def test_tenant_rule_runs_from_cli():
    out = subprocess.run(
        [sys.executable, "-m", "tools.dlint", "--only", "tenant-reasons"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "tenant-reasons" in out.stdout


# -- cycle-robustness regressions (review findings) ---------------------------

def test_ownership_violation_behind_call_cycle_found_for_every_entry(tmp_path):
    """A cycle in the pass-through call graph must not hide a violation
    from LATER entry points (the memo-under-cycle-cut bug): both
    _on_stall and _fail_all reach the loop-owned mutator through the
    chainB<->chainC cycle, and each finding's trail must name its OWN
    entry point."""
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/kvblocks.py": """\
            class BlockPool:
                def alloc(self):  # dlint: owner=loop-thread
                    return 1
            """,
        "dllama_tpu/runtime/serving.py": """\
            class Sched:
                def _on_stall(self, info):  # dlint: owner=monitor-thread
                    self.chain_c()

                def _fail_all(self, msg):  # dlint: owner=any
                    self.chain_b()

                def chain_b(self):
                    self.chain_c()

                def chain_c(self):
                    self.chain_b()
                    self.pool.alloc()

                def _on_crash(self, exc):  # dlint: owner=loop-thread
                    pass
            """,
        "dllama_tpu/runtime/watchdog.py": "",
    })
    res = _run("thread-ownership", project)
    by_entry = {f.lineno: f.message for f in res.findings}
    assert set(by_entry) == {2, 5}            # _on_stall AND _fail_all
    assert "Sched._on_stall" in by_entry[2]
    assert "Sched._fail_all" in by_entry[5]   # its own trail, not a stale one
    assert "Sched._on_stall" not in by_entry[5]


def test_lock_order_edges_survive_call_cycles_and_site_order(tmp_path):
    """Transitive lock sets are a fixpoint, not a cycle-cut memo: the
    earlier hold-site visiting the h<->k cycle must not cache an empty
    set for k and hide the later site's edge (detection would otherwise
    depend on call-site order)."""
    project = _tree(tmp_path, {
        "dllama_tpu/runtime/locky.py": """\
            import threading


            class G:
                def __init__(self):
                    self._l1 = threading.Lock()
                    self._l2 = threading.Lock()
                    self._l3 = threading.Lock()

                def h(self):
                    with self._l1:
                        self.k()

                def k(self):
                    self.h()

                def early_site(self):
                    with self._l3:
                        self.h()

                def late_site(self):
                    with self._l2:
                        self.k()
            """,
    })
    res = _run("lock-order", project)
    # h() holds _l1 and (via the cycle) re-enters itself: self-deadlock
    assert any("self-deadlock" in f.message and "G._l1" in f.message
               for f in res.findings)
    # and the late site's l2->l1 edge must feed cycle detection: prove
    # the edge exists by closing the loop l1->l2 and expecting a cycle
    files2 = {
        "dllama_tpu/runtime/locky.py": (tmp_path / "dllama_tpu/runtime/locky.py").read_text().replace(
            "    def k(self):\n        self.h()\n",
            "    def k(self):\n        self.h()\n\n"
            "    def close_loop(self):\n"
            "        with self._l1:\n"
            "            self.late_site()\n"),
    }
    project2 = _tree(tmp_path, files2)
    res2 = _run("lock-order", project2)
    assert any("cycle" in f.message and "G._l1" in f.message
               and "G._l2" in f.message for f in res2.findings)


def test_non_utf8_file_is_reported_not_crashed(tmp_path):
    (tmp_path / "dllama_tpu" / "runtime").mkdir(parents=True)
    (tmp_path / "dllama_tpu" / "runtime" / "binary.py").write_bytes(
        b"x = 1  # caf\xe9 in latin-1\n")
    project = Project(tmp_path)
    res = _run("exception-hygiene", project)
    assert res.error is None
    assert any("non-UTF-8" in f.message for f in res.findings)
