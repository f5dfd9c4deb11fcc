"""rapidslint: per-rule firing/non-firing fixtures, suppression and
baseline mechanics, the whole-tree clean gate, and the CLI exit codes.

The fixtures are inline source strings fed straight through the engine —
each rule gets at least one positive (must fire) and one negative (must
stay quiet) so a behavior change in a matcher is caught here before it
lands as a false CI failure (or a silent miss) on the real tree.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from spark_rapids_tpu.analysis.engine import (
    Baseline, Finding, LintEngine, SourceFile,
)
from spark_rapids_tpu.analysis import rules as R
from spark_rapids_tpu.analysis import plan_verify

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "rapidslint.py")


def lint(rule, text, path="spark_rapids_tpu/fixture.py", files=None,
         root=REPO):
    """Run one rule over inline fixture source, return findings."""
    srcs = files if files is not None else [(path, text)]
    sfs = [SourceFile(os.path.join(root, p), p, textwrap.dedent(t))
           for p, t in srcs]
    return LintEngine([rule]).run(sfs, root)


def rule_ids(findings):
    return [f.rule_id for f in findings]


# -- R1: import-time jnp construction -----------------------------------------

def test_r1_fires_on_module_scope_jnp():
    out = lint(R.ImportTimeJnpRule(), """\
        import jax.numpy as jnp
        LOOKUP = jnp.zeros((4,), dtype=jnp.int32)
        """)
    assert rule_ids(out) == ["R1"]
    assert "import time" in out[0].message


def test_r1_fires_inside_class_body_and_conditional():
    out = lint(R.ImportTimeJnpRule(), """\
        import jax.numpy as jnp
        class K:
            TABLE = jnp.arange(8)
        if True:
            OTHER = jax.numpy.ones(3)
        """)
    assert rule_ids(out) == ["R1", "R1"]


def test_r1_quiet_inside_functions_and_lambdas():
    out = lint(R.ImportTimeJnpRule(), """\
        import jax.numpy as jnp
        def build():
            return jnp.zeros((4,))
        make = lambda: jnp.ones(2)
        SHAPE = (4, 4)  # plain tuple at import time is fine
        """)
    assert out == []


# -- R2: semaphore release in finally -----------------------------------------

def test_r2_fires_on_unpaired_acquire():
    out = lint(R.SemaphoreReleaseRule(), """\
        def stage(ctx, hb):
            ctx.semaphore.acquire()
            return push(hb)
        """)
    assert rule_ids(out) == ["R2"]
    assert "finally" in out[0].message


def test_r2_quiet_when_release_in_finally():
    out = lint(R.SemaphoreReleaseRule(), """\
        def stage(ctx, hb):
            ctx.semaphore.acquire()
            try:
                return push(hb)
            finally:
                ctx.semaphore.release()
        """)
    assert out == []


def test_r2_quiet_on_non_semaphore_acquire():
    # plain lock acquire/release pairs are not this rule's business
    out = lint(R.SemaphoreReleaseRule(), """\
        def locked(self):
            self._lock.acquire()
            self._lock.release()
        """)
    assert out == []


# -- R3: unbounded waits ------------------------------------------------------

def test_r3_fires_on_unbounded_primitives():
    out = lint(R.UnboundedWaitRule(), """\
        def run(cond, t, self):
            cond.wait()
            t.join()
            self._q.get()
        """)
    assert rule_ids(out) == ["R3", "R3", "R3"]


def test_r3_quiet_with_timeouts_and_non_queue_get():
    out = lint(R.UnboundedWaitRule(), """\
        def run(cond, t, q, d):
            cond.wait(0.25)
            t.join(timeout=5.0)
            q.get(timeout=1.0)
            d.get()  # receiver is not queue-shaped: dict-style get
        """)
    assert out == []


# -- R4: swallowed KeyboardInterrupt/SystemExit -------------------------------

def test_r4_fires_on_bare_except_and_base_exception():
    out = lint(R.SwallowBaseExceptionRule(), """\
        def f():
            try:
                work()
            except:
                pass
            try:
                work()
            except BaseException as e:
                log(e)
        """)
    assert rule_ids(out) == ["R4", "R4"]


def test_r4_quiet_on_reraise_exit_and_narrow_handler():
    out = lint(R.SwallowBaseExceptionRule(), """\
        import os, sys
        def f():
            try:
                work()
            except BaseException:
                raise
            try:
                work()
            except BaseException:
                os._exit(1)
            try:
                work()
            except Exception:
                pass  # cannot catch KI/SE — fine
        """)
    assert out == []


# -- R5: donation hygiene -----------------------------------------------------

def test_r5_fires_on_raw_jit_and_stray_donation():
    out = lint(R.DonationHygieneRule(), """\
        import jax
        def compile_it(f):
            g = jax.jit(f)
            h = jax.jit(f, donate_argnums=(0,))
            return g, h
        """)
    assert rule_ids(out) == ["R5", "R5"]


def test_r5_quiet_on_instrumented_jit_and_registry_file():
    out = lint(R.DonationHygieneRule(), """\
        from spark_rapids_tpu.utils.compile_registry import instrumented_jit
        def compile_it(f):
            return instrumented_jit(f, donate_argnums=(0,))
        """)
    assert out == []
    # the registry module itself is the one sanctioned jax.jit call site
    out = lint(R.DonationHygieneRule(), """\
        import jax
        def _wrap(f):
            return jax.jit(f)
        """, path=R.DonationHygieneRule.ALLOWED_FILE)
    assert out == []


# -- R6: device sync under DeviceRuntime._lock --------------------------------

def test_r6_fires_on_sync_inside_runtime_lock():
    out = lint(R.SyncUnderRuntimeLockRule(), """\
        import jax, threading
        class DeviceRuntime:
            _lock = threading.Lock()
            @classmethod
            def snap(cls, buf):
                with cls._lock:
                    return jax.device_get(buf)
        """)
    assert rule_ids(out) == ["R6"]
    assert "_lock" in out[0].message


def test_r6_quiet_when_sync_moved_outside_lock():
    out = lint(R.SyncUnderRuntimeLockRule(), """\
        import jax, threading
        class DeviceRuntime:
            _lock = threading.Lock()
            @classmethod
            def snap(cls, buf):
                with cls._lock:
                    ref = buf
                return jax.device_get(ref)
        class Other:
            _lock = threading.Lock()
            def ok(self, buf):
                # not DeviceRuntime's lock: out of scope for R6
                with self._lock:
                    return jax.device_get(buf)
        """)
    assert out == []


# -- R7: conf-registry sync ---------------------------------------------------

def test_r7_fires_on_dead_conf_and_unregistered_literal():
    out = lint(R.ConfRegistrySyncRule(), None, files=[
        ("spark_rapids_tpu/config.py", """\
            DEAD = conf_bool("spark.rapids.test.deadKnob", True, "unused")
            LIVE = conf_int("spark.rapids.test.liveKnob", 4, "used")
            """),
        ("spark_rapids_tpu/user.py", """\
            from spark_rapids_tpu.config import LIVE
            def f(conf):
                conf.set("spark.rapids.test.notRegistered", "1")
                return LIVE.get(conf)
            """),
    ])
    msgs = [f.message for f in out]
    assert len(msgs) == 2
    assert any("dead conf" in m and "deadKnob" in m for m in msgs)
    assert any("not registered" in m and "notRegistered" in m for m in msgs)


def test_r7_quiet_on_registered_and_referenced_keys():
    out = lint(R.ConfRegistrySyncRule(), None, files=[
        ("spark_rapids_tpu/config.py", """\
            LIVE = conf_int("spark.rapids.test.liveKnob", 4, "used")
            '''docstring mentioning spark.rapids.test.proseOnly is fine'''
            """),
        ("spark_rapids_tpu/user.py", """\
            from spark_rapids_tpu.config import LIVE
            def f(conf, name):
                key = f"spark.rapids.sql.exec.{name}"  # dynamic family
                return LIVE.get(conf), conf.lookup(key)
            def g(conf):
                # prefix literal covering a registered key
                return conf.starts("spark.rapids.test.")
            """),
    ])
    assert out == []


# -- R8: metrics-key sync -----------------------------------------------------

_SESSION_FIXTURE = """\
    class S:
        def execute(self):
            self.last_metrics["compileCount"] = 1
            self.last_metrics["dispatchCount"] = 2
    """

def _write_doc(root, keys):
    os.makedirs(os.path.join(root, "docs"), exist_ok=True)
    rows = "\n".join(f"| `{k}` | doc |" for k in keys)
    with open(os.path.join(root, "docs", "metrics.md"), "w") as f:
        f.write("| Key | Meaning |\n|---|---|\n" + rows + "\n")


def test_r8_quiet_when_in_sync(tmp_path):
    root = str(tmp_path)
    _write_doc(root, ["compileCount", "dispatchCount"])
    out = lint(R.MetricsKeySyncRule(), None, root=root, files=[
        ("spark_rapids_tpu/session.py", _SESSION_FIXTURE),
    ])
    assert out == []


def test_r8_fires_on_each_drift_direction(tmp_path):
    root = str(tmp_path)
    # doc omits dispatchCount and documents a phantom key
    _write_doc(root, ["compileCount", "phantomKey"])
    out = lint(R.MetricsKeySyncRule(), None, root=root, files=[
        ("spark_rapids_tpu/session.py", _SESSION_FIXTURE),
    ])
    msgs = [f.message for f in out]
    assert len(msgs) == 2, msgs
    assert any("dispatchCount" in m and "undocumented" in m for m in msgs)
    assert any("phantomKey" in m and "never carries" in m for m in msgs)


def test_r8_fires_when_doc_missing(tmp_path):
    out = lint(R.MetricsKeySyncRule(), None, root=str(tmp_path), files=[
        ("spark_rapids_tpu/session.py", _SESSION_FIXTURE),
    ])
    assert len(out) == 1 and "missing" in out[0].message


# -- R9: pallas kernel tier ---------------------------------------------------

def test_r9_fires_outside_tier_entry_points():
    out = lint(R.PallasKernelTierRule(), """\
        from jax.experimental import pallas as pl
        def rogue_kernel(x):
            return pl.pallas_call(lambda r, o: None,
                                  out_shape=x)(x)
        """, path="spark_rapids_tpu/exprs/strings.py")
    assert rule_ids(out) == ["R9"]
    assert "pallas_tier" in out[0].message


def test_r9_quiet_in_tier_entry_points():
    src = """\
        from jax.experimental import pallas as pl
        def kernel(x):
            return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
        """
    for allowed in ("spark_rapids_tpu/kernels/pallas_tier.py",
                    "spark_rapids_tpu/kernels/pallas_strings.py"):
        assert lint(R.PallasKernelTierRule(), src, path=allowed) == []


def test_r9_quiet_on_unrelated_calls():
    out = lint(R.PallasKernelTierRule(), """\
        def fine(x):
            return pallas_callback(x)  # not pallas_call
        """, path="spark_rapids_tpu/kernels/layout.py")
    assert out == []


# -- suppressions and baseline mechanics --------------------------------------

def test_line_suppression_silences_one_rule_only():
    out = lint(R.UnboundedWaitRule(), """\
        def run(cond):
            cond.wait()  # rapidslint: disable=R3
            cond.wait()
        """)
    assert len(out) == 1 and out[0].line == 3


def test_file_suppression_silences_whole_file():
    out = lint(R.UnboundedWaitRule(), """\
        # rapidslint: disable-file=R3
        def run(cond):
            cond.wait()
        """)
    assert out == []


def test_suppression_for_other_rule_does_not_apply():
    out = lint(R.UnboundedWaitRule(), """\
        def run(cond):
            cond.wait()  # rapidslint: disable=R4
        """)
    assert rule_ids(out) == ["R3"]


def test_baseline_matches_by_line_text_not_number():
    f = Finding("R3", "a.py", 42, "msg")
    f.line_text = "    cond.wait()   "
    bl = Baseline([{"rule": "R3", "path": "a.py", "line": "cond.wait()",
                    "reason": "ok"}])
    new, used, stale = bl.partition([f])
    assert new == [] and stale == [] and len(used) == 1


def test_baseline_stale_entry_detected():
    bl = Baseline([{"rule": "R3", "path": "gone.py",
                    "line": "cond.wait()", "reason": "ok"}])
    new, used, stale = bl.partition([])
    assert new == [] and used == [] and len(stale) == 1


def test_baseline_reasons_all_filled_in():
    with open(os.path.join(REPO, "tools", "rapidslint_baseline.json")) as f:
        entries = json.load(f)["findings"]
    assert entries, "baseline unexpectedly empty"
    for e in entries:
        assert e.get("reason") and "TODO" not in e["reason"], \
            f"baseline entry without a justification: {e}"


# -- whole-tree gate and CLI --------------------------------------------------

def _run_cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, cwd=cwd)


def test_tree_is_clean_against_baseline():
    p = _run_cli("--check")
    assert p.returncode == 0, f"lint gate failed:\n{p.stdout}{p.stderr}"
    assert "clean" in p.stdout


def test_cli_rules_catalog_lists_all_rules():
    p = _run_cli("--rules")
    assert p.returncode == 0
    for rid in ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9"):
        assert rid in p.stdout


def _make_tree(tmp_path, bad_source):
    root = tmp_path / "fake_repo"
    pkg = root / "spark_rapids_tpu"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(textwrap.dedent(bad_source))
    (root / "tools").mkdir()
    (root / "ci").mkdir()
    bl = root / "baseline.json"
    bl.write_text('{"findings": []}')
    return str(root), str(bl)


@pytest.mark.parametrize("bad", [
    "import jax.numpy as jnp\nX = jnp.zeros(4)\n",                      # R1
    "def f(ctx):\n    ctx.semaphore.acquire()\n",                       # R2
    "def f(t):\n    t.join()\n",                                        # R3
    "def f():\n    try:\n        g()\n    except:\n        pass\n",     # R4
    "import jax\ndef f(g):\n    return jax.jit(g)\n",                   # R5
    ("import jax, threading\n"
     "class DeviceRuntime:\n"
     "    _lock = threading.Lock()\n"
     "    def f(self, b):\n"
     "        with self._lock:\n"
     "            return jax.device_get(b)\n"),                         # R6
    'K = conf_int("spark.rapids.test.dead", 1, "never read")\n',        # R7
    ("from jax.experimental import pallas as pl\n"
     "def f(x):\n"
     "    return pl.pallas_call(g, out_shape=x)(x)\n"),                 # R9
], ids=["R1", "R2", "R3", "R4", "R5", "R6", "R7", "R9"])
def test_cli_rejects_injected_regression(tmp_path, bad):
    root, bl = _make_tree(tmp_path, bad)
    p = _run_cli("--check", "--root", root, "--baseline", bl)
    assert p.returncode == 1, f"injected regression not caught:\n{p.stdout}"


def test_cli_rejects_injected_r8_regression(tmp_path):
    # R8 needs the session fixture: a metrics key with no doc at all
    root, bl = _make_tree(
        tmp_path,
        "class S:\n"
        "    def execute(self):\n"
        "        self.last_metrics[\"compileCount\"] = 1\n")
    os.rename(os.path.join(root, "spark_rapids_tpu", "bad.py"),
              os.path.join(root, "spark_rapids_tpu", "session.py"))
    p = _run_cli("--check", "--root", root, "--baseline", bl)
    assert p.returncode == 1
    assert "metrics" in p.stdout


def test_cli_rejects_stale_baseline(tmp_path):
    root, bl = _make_tree(tmp_path, "X = 1\n")
    with open(bl, "w") as f:
        json.dump({"findings": [{"rule": "R3", "path": "gone.py",
                                 "line": "q.get()", "reason": "old"}]}, f)
    p = _run_cli("--check", "--root", root, "--baseline", bl)
    assert p.returncode == 1
    assert "stale" in p.stdout


def test_cli_flags_syntax_error_file(tmp_path):
    root, bl = _make_tree(tmp_path, "def broken(:\n")
    p = _run_cli("--check", "--root", root, "--baseline", bl)
    assert p.returncode == 1
    assert "does not parse" in p.stdout


def test_lint_gate_is_runtime_free():
    # the CI gate's 15s budget depends on never importing jax; run a
    # whole --check in-process and prove the property instead of
    # trusting comments
    code = ("import sys\n"
            "sys.argv = ['rapidslint', '--check']\n"
            "import runpy\n"
            "try:\n"
            f"    runpy.run_path({CLI!r}, run_name='__main__')\n"
            "except SystemExit:\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'lint gate imported jax'\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO)
    assert p.returncode == 0, p.stdout + p.stderr


# -- plan_verify fixtures -----------------------------------------------------

class _FakeField:
    def __init__(self, name, dtype="int"):
        self.name = name
        self.dtype = dtype

    def __repr__(self):
        return f"{self.name}:{self.dtype}"


class _FakeSchema:
    def __init__(self, *names, dtype="int"):
        self.fields = tuple(_FakeField(n, dtype) for n in names)


class _FakeOp:
    is_tpu = False

    def __init__(self, *children, schema=None):
        self.children = list(children)
        self.output_schema = schema or _FakeSchema("a")
        self.op_id = f"{type(self).__name__}@fake"


def test_plan_verify_accepts_well_formed_tree():
    plan_verify.verify_plan(_FakeOp(_FakeOp()))


def test_plan_verify_rejects_duplicate_columns():
    bad = _FakeOp(schema=_FakeSchema("a", "a"))
    with pytest.raises(plan_verify.PlanInvariantError,
                       match="duplicate output columns"):
        plan_verify.verify_plan(bad)


def test_plan_verify_rejects_missing_dtype():
    bad = _FakeOp(schema=_FakeSchema("a", dtype=None))
    with pytest.raises(plan_verify.PlanInvariantError, match="no dtype"):
        plan_verify.verify_plan(bad)


def test_plan_verify_rejects_unmediated_boundary():
    child = _FakeOp()
    child.is_tpu = True
    parent = _FakeOp(child)  # CPU parent fed by TPU child, no transition
    with pytest.raises(plan_verify.PlanInvariantError,
                       match="without a HostToDevice/DeviceToHost"):
        plan_verify.verify_plan(parent)


def test_plan_verify_rejects_bad_donation_provenance():
    src = _FakeOp()  # neither stage-break nor HostToDeviceExec
    root = _FakeOp(src)
    root._stage_builds = {"default": ([src], None)}
    root._stage_cache = {("default", None, (True,)): object()}
    with pytest.raises(plan_verify.PlanInvariantError,
                       match="donates source"):
        plan_verify.verify_plan(root)


def test_plan_verify_accepts_stage_break_donation():
    src = _FakeOp()
    src.pipeline_stage_break = True
    root = _FakeOp(src)
    root._stage_builds = {"default": ([src], None)}
    root._stage_cache = {("default", None, (True,)): object()}
    plan_verify.verify_plan(root)


def test_plan_verify_semaphore_balance():
    class _Sem:
        def __init__(self, depth):
            self._d = depth

        def held_depth(self):
            return self._d

    class _Runtime:
        def __init__(self, depth):
            self.semaphore = _Sem(depth)

    plan_verify.verify_plan(_FakeOp(), runtime=_Runtime(0))
    with pytest.raises(plan_verify.PlanInvariantError,
                       match="leaked device admission"):
        plan_verify.verify_plan(_FakeOp(), runtime=_Runtime(2))


def test_plan_verify_on_a_real_executed_plan():
    # end-to-end: run a query, then verify the session's actual plan
    from compare import tpu_session
    from spark_rapids_tpu import types as T
    s = tpu_session()
    df = s.create_dataframe({"a": (T.INT, [1, 2, 3, 4, 5, 6]),
                             "b": (T.LONG, [10, 20, 30, 40, 50, 60])},
                            num_partitions=2)
    df.filter(df["a"] > 2).select("a", "b").collect()
    plan_verify.verify_session(s)
