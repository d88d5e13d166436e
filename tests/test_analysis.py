"""Static-analysis suite (graftlint + plan verifier + backend classifier).

Four layers:

* **Rule fixtures** — one known-bad and one known-good snippet per lint
  rule, plus the ``# graftlint: disable=`` escape hatch.
* **Repo-tree gate** — the tier-1 sweep: a new donated-aliasing /
  trace-safety / config-key / fence violation anywhere in the tree fails
  this test before it ships (``scripts/lint.py`` runs the same sweep).
* **Plan verifier** — zero violations across the entire committed
  golden-plan corpus, and tampered plans (broken windows, unknown serdes,
  dangling column refs, key-arity mismatches) are caught.
* **Backend classification** — the breadth slice's ahead-of-time
  placement is pinned in tests/backend_snapshot.json (regenerate with
  ``scripts/gen_backend_snapshot.py``), and the static decision is checked
  against the REAL runtime fallback ladder (executor constructors) —
  sampled here, full corpus under ``-m slow``.  The golden corpus is
  replanned from the QTT suite, so the sweep covers every QTT query shape
  tier-1 exercises.
"""

import copy
import json
import os
import subprocess
import sys
import textwrap

import pytest

from ksql_tpu.analysis import (
    LintModule,
    classify_plan,
    default_rules,
    lint_modules,
    lint_paths,
    lint_source,
    verify_plan,
)
from ksql_tpu.analysis.rules_aliasing import DonatedAliasingRule
from ksql_tpu.analysis.rules_race import SharedStateRaceRule
from ksql_tpu.analysis.rules_retrace import JitRetraceRule
from ksql_tpu.execution.steps import plan_from_json
from ksql_tpu.functions.registry import FunctionRegistry
from ksql_tpu.tools.golden_plans import (
    BREADTH_FILES,
    GOLDEN_DIR,
    SNAPSHOT_PATH,
    classify_corpus,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(snippet):
    return {f.rule for f in lint_source(textwrap.dedent(snippet))}


# ------------------------------------------------------------ rule fixtures

ALIASING_BAD_STORE = """
    import numpy as np
    import jax.numpy as jnp

    class Dev:
        def restore(self, flat):
            self.state = {k: jnp.asarray(np.frombuffer(v))
                          for k, v in flat.items()}
"""

ALIASING_BAD_DONATED_CALL = """
    import jax
    import numpy as np

    class Dev:
        def __init__(self, step):
            self._step = jax.jit(step, donate_argnums=0)

        def run(self, rows):
            state = np.zeros((4,))
            return self._step(state, rows)
"""

ALIASING_GOOD = """
    import numpy as np
    import jax.numpy as jnp

    class Dev:
        def restore(self, flat):
            # jnp.array COPIES the host buffer: donation-safe
            self.state = {k: jnp.array(np.frombuffer(v))
                          for k, v in flat.items()}
"""

TRACE_BAD = """
    import time

    class Dev:
        def _trace_step(self, state, arrays):
            t = time.time()
            self.compiles += 1
            return state
"""

TRACE_GOOD = """
    import jax.numpy as jnp

    class Dev:
        def _trace_step(self, state, arrays):
            cap = self.capacity  # trace-time statics are fine to READ
            return {k: jnp.where(arrays["live"], v, v) for k, v in state.items()}
"""

CONFIG_BAD = """
    def setup(config):
        return config.get("ksql.graftlint.not.a.registered.key")
"""

CONFIG_GOOD = """
    def setup(config):
        return config.get("ksql.service.id")
"""

FENCE_BAD = """
    def tick(handle):
        consumer = handle.consumer

        def alive():
            return handle.consumer is consumer

        handle.restart_count = 0
        if alive():
            handle.epoch = {}
"""

FENCE_GOOD = """
    def tick(handle):
        consumer = handle.consumer

        def alive():
            return handle.consumer is consumer

        if not alive():
            return
        handle.restart_count = 0
        if alive():
            handle.poison_skip.add(1)
"""


def test_aliasing_rule_flags_host_store_into_state():
    assert "donated-aliasing" in _rules(ALIASING_BAD_STORE)


def test_aliasing_rule_flags_host_buffer_at_donated_position():
    assert "donated-aliasing" in _rules(ALIASING_BAD_DONATED_CALL)


def test_aliasing_rule_accepts_copies():
    assert "donated-aliasing" not in _rules(ALIASING_GOOD)


def test_trace_rule_flags_clock_and_self_mutation():
    findings = [f for f in lint_source(textwrap.dedent(TRACE_BAD))
                if f.rule == "trace-unsafe"]
    assert len(findings) == 2  # time.time() + self.compiles += 1


def test_trace_rule_accepts_pure_trace_bodies():
    assert "trace-unsafe" not in _rules(TRACE_GOOD)


def test_config_rule_flags_unregistered_key_reads():
    assert "unregistered-config-key" in _rules(CONFIG_BAD)


def test_config_rule_accepts_registered_keys():
    assert "unregistered-config-key" not in _rules(CONFIG_GOOD)


def test_fence_rule_flags_unguarded_handle_mutation():
    findings = [f for f in lint_source(textwrap.dedent(FENCE_BAD))
                if f.rule == "unfenced-handle-mutation"]
    assert len(findings) == 1  # restart_count only; the guarded epoch is fine


def test_fence_rule_accepts_guards_and_bailouts():
    assert "unfenced-handle-mutation" not in _rules(FENCE_GOOD)


def test_escape_hatch_covers_innermost_statement_only():
    # a disable trailing an UNRELATED line inside a compound body must not
    # suppress a finding anchored at the compound statement's header line
    snippet = textwrap.dedent("""
        def tick(handle):
            consumer = handle.consumer

            def alive():
                return handle.consumer is consumer

            for _ in range(handle.poison_skip.pop()):
                other = 1  # graftlint: disable=unfenced-handle-mutation
    """)
    findings = [f for f in lint_source(snippet)
                if f.rule == "unfenced-handle-mutation"]
    assert len(findings) == 1  # the pop() in the for header stays flagged


def test_escape_hatch_line_and_file_suppression():
    flagged = textwrap.dedent(ALIASING_BAD_STORE)
    line = flagged.replace(
        "for k, v in flat.items()}",
        "for k, v in flat.items()}  # graftlint: disable=donated-aliasing",
    )
    assert not lint_source(line)
    filewide = "# graftlint: disable-file=donated-aliasing\n" + flagged
    assert not lint_source(filewide)
    # suppression is per-rule: disabling another rule keeps the finding
    other = flagged.replace(
        "for k, v in flat.items()}",
        "for k, v in flat.items()}  # graftlint: disable=trace-unsafe",
    )
    assert lint_source(other)


# -------------------------------------------- interprocedural aliasing

# the cross-function handoff the per-function pass PROVABLY misses: the
# sink store lives in the callee, so taint dies at the call boundary
ALIASING_XFN_BAD = """
    import numpy as np

    class Dev:
        def _install(self, buf):
            self.state = buf

        def restore(self, blob):
            self._install(np.frombuffer(blob))
"""

ALIASING_XFN_GOOD = """
    import numpy as np
    import jax.numpy as jnp

    class Dev:
        def _install(self, buf):
            self.state = buf

        def restore(self, blob):
            self._install(jnp.array(np.frombuffer(blob)))
"""

# three-hop helper chain: settles through the two-pass summaries
ALIASING_CHAIN_BAD = """
    import numpy as np

    class Dev:
        def _leaf(self, x):
            self.state = x

        def _mid(self, y):
            self._leaf(y)

        def top(self, blob):
            self._mid(np.frombuffer(blob))
"""

# cross-MODULE handoff: the helper stores into donated state in another
# file (the store-grow/rebuild -> lowering shape ROADMAP said to audit
# by hand)
XMOD_HELPER = """
    def install_state(dev, buf):
        dev.state = buf
"""

XMOD_CALLER_BAD = """
    import numpy as np
    from pkg.helper import install_state

    def restore(dev, blob):
        install_state(dev, np.frombuffer(blob))
"""

XMOD_CALLER_GOOD = """
    import numpy as np
    import jax.numpy as jnp
    from pkg.helper import install_state

    def restore(dev, blob):
        install_state(dev, jnp.array(np.frombuffer(blob)))
"""


def _per_fn(snippet):
    return lint_source(textwrap.dedent(snippet),
                       rules=[DonatedAliasingRule(interprocedural=False)])


def _inter(snippet):
    return lint_source(textwrap.dedent(snippet),
                       rules=[DonatedAliasingRule()])


def test_interprocedural_flags_cross_function_handoff_per_function_misses():
    """Pinned BOTH ways: the frozen PR-6 per-function pass does NOT see
    the helper-mediated handoff (taint dies at the call), the
    whole-program pass does."""
    assert not _per_fn(ALIASING_XFN_BAD)
    flagged = _inter(ALIASING_XFN_BAD)
    assert flagged and all(f.rule == "donated-aliasing" for f in flagged)
    assert "_install" in flagged[0].message


def test_interprocedural_accepts_copied_handoff():
    assert not _inter(ALIASING_XFN_GOOD)


def test_interprocedural_follows_helper_chains():
    assert not _per_fn(ALIASING_CHAIN_BAD)
    assert _inter(ALIASING_CHAIN_BAD)


def _xmod_modules(caller):
    return [
        LintModule("/tmp/pkg/caller.py", textwrap.dedent(caller)),
        LintModule("/tmp/pkg/helper.py", textwrap.dedent(XMOD_HELPER)),
    ]


def test_interprocedural_crosses_module_boundaries():
    flagged = lint_modules(_xmod_modules(XMOD_CALLER_BAD),
                           [DonatedAliasingRule()])
    assert flagged and flagged[0].path.endswith("caller.py")
    assert "install_state" in flagged[0].message
    # per-function mode: blind to the import
    assert not lint_modules(_xmod_modules(XMOD_CALLER_BAD),
                            [DonatedAliasingRule(interprocedural=False)])
    # the copying caller is clean in both modes
    assert not lint_modules(_xmod_modules(XMOD_CALLER_GOOD),
                            [DonatedAliasingRule()])


def test_sink_attribution_is_differential_not_blanket():
    """Review finding (PR 8): a callee with a PARAM-INDEPENDENT internal
    finding (unconditional host store) must not mark its parameters as
    sinks — callers passing host buffers to non-sink parameters stay
    clean, and callers are still flagged at the callee's own line only."""
    snippet = """
        import numpy as np

        class Dev:
            def setup(self, cfg):
                self.state = np.zeros(4)   # internal, param-independent
                self.mode = cfg

            def boot(self, blob):
                self.setup(np.frombuffer(blob))
    """
    flagged = _inter(snippet)
    # exactly the internal store is flagged; the boot() call site is NOT
    # (cfg never reaches donated state)
    assert len(flagged) == 1, [f.format() for f in flagged]
    assert "self.state" in flagged[0].message


def test_interprocedural_sweep_reaches_real_grow_rebuild_handoff():
    """The audited store-grow/rebuild handoff (lowering._regrow_ring — a
    hand-audit case the old ROADMAP hazard note named) is genuinely
    REACHED by the sweep: reverting its jnp.array copy to zero-copy
    asarray is caught.  Guards against the sweep going vacuously clean
    through a resolution regression."""
    path = os.path.join(REPO_ROOT, "ksql_tpu", "runtime", "lowering.py")
    with open(path) as f:
        src = f.read()
    needle = "self.state = {k: jnp.array(v) for k, v in new.items()}"
    assert needle in src  # the PR-2/PR-6 fix is still in place
    bad = src.replace(needle, needle.replace("jnp.array", "jnp.asarray"), 1)
    flagged = lint_source(bad, path, rules=[DonatedAliasingRule()])
    assert any(f.rule == "donated-aliasing" for f in flagged), flagged


def test_per_function_findings_are_a_subset_of_interprocedural():
    """The whole-program pass only ever ADDS findings: every fixture the
    per-function pass flags stays flagged (resolution failures cost
    recall, never precision), and the cross-function fixtures make the
    inclusion strict."""
    fixtures = [ALIASING_BAD_STORE, ALIASING_BAD_DONATED_CALL,
                ALIASING_GOOD, ALIASING_XFN_BAD, ALIASING_CHAIN_BAD,
                ALIASING_XFN_GOOD]
    mods = [LintModule(f"/tmp/subset/m{i}.py", textwrap.dedent(s))
            for i, s in enumerate(fixtures)]
    def run(rule):
        return {(f.path, f.line, f.rule)
                for f in lint_modules(mods, [rule])}
    per_fn = run(DonatedAliasingRule(interprocedural=False))
    inter = run(DonatedAliasingRule())
    assert per_fn < inter  # strict subset: same findings + the new reach


# ------------------------------------------------- shared-state-race

RACE_BAD = """
    import threading

    class Server:
        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()

        def _loop(self):
            while True:
                self.counter += 1

        def handle(self):
            self.counter = 0
"""

RACE_GOOD_LOCK = """
    import threading

    class Server:
        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()

        def _loop(self):
            with self._lock:
                self.counter += 1

        def handle(self):
            with self._lock:
                self.counter = 0
"""

RACE_GOOD_OWNER = """
    import threading

    class Server:
        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()

        def _loop(self):
            # reviewed: only the loop thread ever writes the counter
            self.counter += 1  # graftlint: owner=loop

        def handle(self):
            return self.counter
"""

RACE_GOOD_JOINED = """
    import threading

    class Engine:
        def tick(self):
            w = threading.Thread(target=self._body, daemon=True)
            w.start()
            w.join(0.1)

        def _body(self):
            self.n += 1

        def handle(self):
            self.n = 0
"""


def test_race_rule_flags_unguarded_two_entrypoint_mutation():
    findings = [f for f in lint_source(textwrap.dedent(RACE_BAD))
                if f.rule == "shared-state-race"]
    assert len(findings) == 2  # the loop += and the handler reset
    assert "Server.counter" in findings[0].message


def test_race_rule_accepts_lock_guard_and_owner_claim():
    assert "shared-state-race" not in _rules(RACE_GOOD_LOCK)
    assert "shared-state-race" not in _rules(RACE_GOOD_OWNER)


def test_race_rule_ignores_joined_workers():
    """A worker its spawner join()s is serialized with it — the
    abandonment window is the fence rule's jurisdiction, not a
    free-running race (the engine's supervised tick/rebuild workers)."""
    assert "shared-state-race" not in _rules(RACE_GOOD_JOINED)


def test_race_rule_binds_entrypoint_annotation_on_decorated_def():
    """The entrypoint= annotation must bind through a decorator — two
    annotation-declared callbacks racing on shared state are caught."""
    snippet = """
        def deco(f):
            return f

        class Hub:
            # graftlint: entrypoint=cb-a
            @deco
            def on_a(self, e):
                self.last = e

            # graftlint: entrypoint=cb-b
            @deco
            def on_b(self, e):
                self.last = e
    """
    findings = [f for f in lint_source(textwrap.dedent(snippet))
                if f.rule == "shared-state-race"]
    assert len(findings) == 2, findings  # both unguarded mutations


def test_race_rule_reports_dangling_entrypoint_annotation():
    """A mark that binds to no def fails LOUD — the author believes the
    concurrency is checked when it silently is not."""
    snippet = """
        import threading

        class Hub:
            def start(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                pass

            # graftlint: entrypoint=worker

            def on_event(self, e):
                self.last = e
    """
    findings = [f for f in lint_source(textwrap.dedent(snippet))
                if f.rule == "shared-state-race"]
    assert any("dangling" in f.message for f in findings), findings


def test_race_rule_rejects_stale_owner_claim():
    """An owner= label naming an entrypoint that cannot reach the
    mutation must NOT suppress."""
    snippet = RACE_GOOD_OWNER.replace("owner=loop", "owner=no-such-thread")
    assert "shared-state-race" in _rules(snippet)


# ------------------------------------------------------- jit-retrace

RETRACE_BRANCH = """
    class Dev:
        def _trace_step(self, state, arrays):
            if arrays["live"].sum() > 0:
                return state
            return state
"""

RETRACE_CONCRETIZE = """
    class Dev:
        def _trace_step(self, state, arrays):
            n = int(arrays["count"])
            return state
"""

RETRACE_ITEM = """
    class Dev:
        def _trace_step(self, state, arrays):
            x = arrays["count"].item()
            return state
"""

RETRACE_FSTRING = """
    class Dev:
        def _trace_step(self, state, arrays):
            key = f"slot_{arrays['idx']}"
            return state[key]
"""

RETRACE_HELPER_CHAIN = """
    class Dev:
        def _helper(self, vals):
            while vals.any():
                vals = vals[:-1]
            return vals

        def _trace_step(self, state, arrays):
            return self._helper(arrays["v"])
"""

RETRACE_STALE_CAPTURE = """
    import jax

    class Dev:
        def __init__(self):
            self.cap = 4
            self._step = jax.jit(self._trace_step)

        def bump(self):
            self.cap *= 2  # mutates WITHOUT recompiling

        def _trace_step(self, state, arrays):
            return state["x"][: self.cap]
"""

RETRACE_OK_RECOMPILES = """
    import jax

    class Dev:
        def __init__(self):
            self.cap = 4
            self._step = jax.jit(self._trace_step)

        def grow(self):
            self.cap *= 2
            self._step = jax.jit(self._trace_step)

        def _trace_step(self, state, arrays):
            return state["x"][: self.cap]
"""

RETRACE_STATIC_PER_BATCH = """
    import jax

    class Dev:
        def __init__(self, fn):
            self._step = jax.jit(fn, static_argnums=1)

        def process(self, rows):
            return self._step(rows, len(rows))
"""

RETRACE_STATIC_UNHASHABLE = """
    import jax

    class Dev:
        def __init__(self, fn):
            self._step = jax.jit(fn, static_argnums=1)

        def process(self, rows):
            return self._step(rows, [1, 2])
"""

RETRACE_GOOD = """
    import jax.numpy as jnp

    class Dev:
        def _trace_step(self, state, arrays):
            if self.agg is None:          # trace-time static
                return state
            if "hpass" in state:          # pytree-structure membership
                state["hpass"] = jnp.where(
                    arrays["live"], 1, state["hpass"]
                )
            opt = state.get("clock")
            if opt is not None:           # Optional plumbing
                state["clock"] = jnp.maximum(opt, arrays["ts"].max())
            return state
"""

RETRACE_STATIC_PARAM_IDIOM = """
    import jax

    class Dev:
        def _compile(self):
            self._l = jax.jit(lambda st, ar: self._trace_side("l", st, ar))

        def _trace_side(self, side: str, state, arrays):
            o = "r" if side == "l" else "l"
            if side == "l":
                return state[f"buf_{o}"]
            return state[f"buf_{side}"]
"""


@pytest.mark.parametrize("snippet,label", [
    (RETRACE_BRANCH, "branch"),
    (RETRACE_CONCRETIZE, "concretize"),
    (RETRACE_ITEM, "item"),
    (RETRACE_FSTRING, "fstring"),
    (RETRACE_HELPER_CHAIN, "helper-chain"),
    (RETRACE_STALE_CAPTURE, "stale-capture"),
    (RETRACE_STATIC_PER_BATCH, "static-per-batch"),
    (RETRACE_STATIC_UNHASHABLE, "static-unhashable"),
])
def test_retrace_rule_flags_each_pattern(snippet, label):
    assert "jit-retrace" in _rules(snippet), label


@pytest.mark.parametrize("snippet,label", [
    (RETRACE_OK_RECOMPILES, "mutate-then-recompile"),
    (RETRACE_GOOD, "pure-trace-body"),
    (RETRACE_STATIC_PARAM_IDIOM, "scalar-static-params"),
])
def test_retrace_rule_accepts_sanctioned_patterns(snippet, label):
    assert "jit-retrace" not in _rules(snippet), label


# ------------------------------------------------ blocking-under-lock

BLOCKING_SLEEP = """
    import threading, time

    def worker(self):
        with self._lock:
            time.sleep(0.5)

    def spawn(self):
        threading.Thread(target=worker).start()
"""

BLOCKING_CHAIN = """
    import os, threading

    def _persist(path):
        os.replace(path, path + ".tmp")

    def flush(self):
        with self.state_lock:
            _persist("x")

    def spawn(self):
        threading.Thread(target=flush).start()
"""

BLOCKING_JIT = """
    import threading, jax

    def rebuild(self):
        with self._lock:
            self._fn = jax.jit(lambda x: x)

    def spawn(self):
        threading.Thread(target=rebuild).start()
"""

BLOCKING_OK_OUTSIDE = """
    import threading, time

    def worker(self):
        time.sleep(0.5)  # blocking, but no lock held
        with self._lock:
            self.n += 1  # graftlint: disable=shared-state-race

    def spawn(self):
        threading.Thread(target=worker).start()
"""

BLOCKING_SINGLE_THREADED = """
    import time

    def f(self):
        with self._lock:
            time.sleep(1)
"""

BLOCKING_CLOSURE_OK = """
    import threading, time

    def make_backoff():
        def waiter():
            time.sleep(1)
        return waiter

    def worker(self):
        with self._lock:
            cb = make_backoff()  # builds the closure; nothing blocks here

    def spawn(self):
        threading.Thread(target=worker).start()
"""

BLOCKING_SUPPRESSED = """
    import threading, time

    def worker(self):
        with self._lock:
            # reviewed: lock exists to serialize exactly this wait
            time.sleep(0.5)  # graftlint: disable=blocking-under-lock

    def spawn(self):
        threading.Thread(target=worker).start()
"""


@pytest.mark.parametrize("snippet,label", [
    (BLOCKING_SLEEP, "direct-sleep"),
    (BLOCKING_CHAIN, "interprocedural-file-io"),
    (BLOCKING_JIT, "jit-compile"),
])
def test_blocking_rule_flags_each_kind(snippet, label):
    assert "blocking-under-lock" in _rules(snippet), label


@pytest.mark.parametrize("snippet,label", [
    (BLOCKING_OK_OUTSIDE, "blocking-outside-lock"),
    (BLOCKING_SINGLE_THREADED, "no-concurrency-machinery"),
    (BLOCKING_SUPPRESSED, "reviewed-suppression"),
    (BLOCKING_CLOSURE_OK, "nested-closure-not-attributed"),
])
def test_blocking_rule_accepts(snippet, label):
    assert "blocking-under-lock" not in _rules(snippet), label


def test_blocking_rule_names_chain_and_entrypoints():
    """The finding must be actionable: it names the blocking kind, the
    call chain that reaches it, and the entrypoints contending on the
    lock (the race rule's map, reused)."""
    findings = [
        f for f in lint_source(textwrap.dedent(BLOCKING_CHAIN))
        if f.rule == "blocking-under-lock"
    ]
    assert len(findings) == 1
    msg = findings[0].message
    assert "file-io" in msg
    assert "_persist" in msg  # the chain
    assert "entrypoints [" in msg  # the race-rule entrypoint map


def test_blocking_rule_crosses_module_boundaries(tmp_path):
    """Interprocedural across files: the lock body calls a helper whose
    blocking IO lives in another module of the same program."""
    (tmp_path / "iohelp.py").write_text(textwrap.dedent("""
        import os

        def persist(path):
            os.replace(path, path + ".bak")
    """))
    (tmp_path / "svc.py").write_text(textwrap.dedent("""
        import threading

        from iohelp import persist

        def flush(self):
            with self._lock:
                persist("x")

        def spawn(self):
            threading.Thread(target=flush).start()
    """))
    findings = lint_paths([str(tmp_path)])
    mine = [f for f in findings if f.rule == "blocking-under-lock"]
    assert len(mine) == 1 and mine[0].path.endswith("svc.py"), findings


# ------------------------------------------------------- repo-tree gate

def test_repo_tree_is_lint_clean():
    """The tier-1 gate: the same sweep scripts/lint.py runs.  A finding
    here is a real violation of a shipped-bug class — fix it or suppress
    with a justified ``# graftlint: disable=<rule>``."""
    findings = lint_paths([os.path.join(REPO_ROOT, p)
                           for p in ("ksql_tpu", "scripts")])
    assert not findings, "\n".join(f.format() for f in findings)


def test_lint_cli_exits_nonzero_on_each_bad_fixture(tmp_path):
    bad = {
        "aliasing": ALIASING_BAD_STORE,
        "trace": TRACE_BAD,
        "config": CONFIG_BAD,
        "fence": FENCE_BAD,
    }
    for name, snippet in bad.items():
        p = tmp_path / f"bad_{name}.py"
        p.write_text(textwrap.dedent(snippet))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint.py"),
             str(p)],
            capture_output=True, text=True, cwd=REPO_ROOT,
        )
        assert proc.returncode == 1, (name, proc.stdout, proc.stderr)
        assert str(p) in proc.stdout
    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent(ALIASING_GOOD))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint.py"),
         str(good)],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)


def test_lint_cli_rejects_nonexistent_path(tmp_path):
    """A typo'd path must be a usage error (exit 2), not a false-clean
    exit 0 — CI wired against a misspelled tree would otherwise lint
    nothing and pass forever."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint.py"),
         str(tmp_path / "no_such_tree")],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    assert "no such path" in proc.stderr


def test_lint_cli_lists_rules():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint.py"),
         "--list-rules"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0
    for rule in default_rules():
        assert rule.name in proc.stdout


def _lint_cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint.py"),
         *args],
        capture_output=True, text=True, cwd=cwd,
    )


def test_lint_cli_threads_report(tmp_path):
    """--threads dumps the entrypoint map: labels, roots, shared keys,
    per-mutation guard status."""
    p = tmp_path / "srv.py"
    p.write_text(textwrap.dedent(RACE_BAD))
    proc = _lint_cli("--threads", str(p))
    assert proc.returncode == 0, proc.stderr
    assert "loop" in proc.stdout and "(thread)" in proc.stdout
    assert "Server.counter" in proc.stdout
    assert "UNGUARDED" in proc.stdout
    # the real tree's map names the concurrency machinery this PR checks
    proc = _lint_cli("--threads",
                     os.path.join(REPO_ROOT, "ksql_tpu", "server"),
                     os.path.join(REPO_ROOT, "ksql_tpu", "engine"),
                     os.path.join(REPO_ROOT, "ksql_tpu", "runtime"))
    assert proc.returncode == 0, proc.stderr
    for label in ("heartbeat_loop", "process_loop", "http",
                  "family-delivery", "(thread-joined)"):
        assert label in proc.stdout, label


def test_lint_cli_baseline_diff_only(tmp_path):
    """--baseline: audited findings stop failing the run; NEW findings
    still do."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(ALIASING_BAD_STORE))
    baseline = tmp_path / "baseline.json"
    # without a baseline: fail
    assert _lint_cli(str(bad)).returncode == 1
    # snapshot the audited state
    proc = _lint_cli("--baseline", str(baseline), "--write-baseline",
                     str(bad))
    assert proc.returncode == 0, proc.stderr
    assert baseline.exists()
    # same findings vs baseline: clean
    proc = _lint_cli("--baseline", str(baseline), str(bad))
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    # a NEW violation fails, and only IT is reported
    worse = tmp_path / "worse.py"
    worse.write_text(textwrap.dedent(TRACE_BAD))
    proc = _lint_cli("--baseline", str(baseline), str(bad), str(worse))
    assert proc.returncode == 1
    assert "NEW finding" in proc.stderr
    assert "worse.py" in proc.stdout and "bad.py" not in proc.stdout
    # missing baseline file is a usage error, not a false-clean
    proc = _lint_cli("--baseline", str(tmp_path / "nope.json"), str(bad))
    assert proc.returncode == 2


def test_lint_cli_parallel_jobs_matches_serial(tmp_path):
    """--jobs N must produce exactly the serial findings (same
    bounded-fixpoint analysis, chunked)."""
    (tmp_path / "helper.py").write_text(textwrap.dedent(XMOD_HELPER))
    (tmp_path / "caller.py").write_text(textwrap.dedent(XMOD_CALLER_BAD))
    (tmp_path / "clean.py").write_text(textwrap.dedent(ALIASING_GOOD))
    (tmp_path / "racy.py").write_text(textwrap.dedent(RACE_BAD))
    serial = _lint_cli(str(tmp_path))
    parallel = _lint_cli("--jobs", "2", str(tmp_path))
    assert serial.returncode == parallel.returncode == 1
    assert serial.stdout == parallel.stdout


def test_lint_cli_parallel_jobs_converges_cross_chunk_chains(tmp_path):
    """Review finding (PR 8): a taint chain whose hops live in DIFFERENT
    worker chunks needs one merged pass per hop — the parallel path must
    iterate to the fixpoint, not stop after a single merged pass.  Four
    files, --jobs 4: one hop per chunk."""
    (tmp_path / "a.py").write_text(textwrap.dedent("""
        def leaf(dev, buf):
            dev.state = buf
    """))
    (tmp_path / "b.py").write_text(textwrap.dedent("""
        from pkg.a import leaf

        def mid2(dev, buf):
            leaf(dev, buf)
    """))
    (tmp_path / "c.py").write_text(textwrap.dedent("""
        from pkg.b import mid2

        def mid(dev, buf):
            mid2(dev, buf)
    """))
    (tmp_path / "d.py").write_text(textwrap.dedent("""
        import numpy as np
        from pkg.c import mid

        def top(dev, blob):
            mid(dev, np.frombuffer(blob))
    """))
    serial = _lint_cli("--rules", "donated-aliasing", str(tmp_path))
    parallel = _lint_cli("--rules", "donated-aliasing", "--jobs", "4",
                         str(tmp_path))
    assert serial.returncode == 1, serial.stdout
    assert "d.py" in serial.stdout
    assert parallel.returncode == 1, (parallel.stdout, parallel.stderr)
    assert serial.stdout == parallel.stdout


# ------------------------------------------------------- plan verifier

def _iter_golden_plans(files=None):
    names = files if files is not None else sorted(os.listdir(GOLDEN_DIR))
    for fname in names:
        with open(os.path.join(GOLDEN_DIR, fname)) as f:
            for case, plans in sorted(json.load(f).items()):
                for qid, pj in sorted(plans.items()):
                    yield fname, case, qid, pj


def _nodes(obj, node_type):
    """Every serialized step/expression dict of the given node type."""
    stack = [obj]
    while stack:
        cur = stack.pop()
        if isinstance(cur, dict):
            if cur.get("node") == node_type:
                yield cur
            stack.extend(cur.values())
        elif isinstance(cur, list):
            stack.extend(cur)


def _first_plan_with(node_type, files=None):
    for fname, case, qid, pj in _iter_golden_plans(files):
        if any(True for _ in _nodes(pj, node_type)):
            return copy.deepcopy(pj)
    raise AssertionError(f"no golden plan contains {node_type}")


def test_golden_corpus_verifies_clean():
    """Every committed golden plan passes static verification — the
    corpus replans the QTT suite, so this sweeps every QTT query shape
    tier-1 exercises."""
    bad = []
    n = 0
    for fname, case, qid, pj in _iter_golden_plans():
        violations = verify_plan(plan_from_json(pj))
        n += 1
        bad.extend(
            f"{fname}/{case}/{qid}: {v.format()}" for v in violations
        )
    assert n > 1500, n  # the sweep really covered the corpus
    assert not bad, bad[:20]


def test_verifier_catches_broken_window():
    pj = _first_plan_with("WindowExpression", ["tumbling-windows.json"])
    for w in _nodes(pj, "WindowExpression"):
        w["fields"]["size_ms"] = -5
    violations = verify_plan(plan_from_json(pj))
    assert any(v.rule == "window-invariant" for v in violations), violations


def test_verifier_catches_unknown_serde_format():
    pj = _first_plan_with("StreamSink", ["project-filter.json"])
    for s in _nodes(pj, "StreamSink"):
        s["fields"]["formats"]["fields"]["value_format"] = "BOGUS"
    violations = verify_plan(plan_from_json(pj))
    assert any(v.rule == "serde-invariant" for v in violations), violations


def test_verifier_catches_dangling_column_reference():
    pj = _first_plan_with("StreamFilter", ["project-filter.json"])
    for flt in _nodes(pj, "StreamFilter"):
        for ref in _nodes(flt["fields"]["predicate"], "ColumnRef"):
            ref["fields"]["name"] = "GRAFT_NO_SUCH_COLUMN"
    violations = verify_plan(plan_from_json(pj))
    assert any(v.rule == "schema-propagation" for v in violations), violations


def test_verifier_catches_projection_alias_mismatch():
    pj = _first_plan_with("StreamSelect", ["project-filter.json"])
    node = next(iter(_nodes(pj, "StreamSelect")))
    cols = node["fields"]["schema"]["schema"]["valueColumns"]
    cols[0]["name"] = "GRAFT_RENAMED"
    violations = verify_plan(plan_from_json(pj))
    assert any(v.rule == "schema-propagation" for v in violations), violations


def test_verifier_catches_repartition_key_arity_mismatch():
    pj = _first_plan_with("StreamSelectKey", ["partition-by.json"])
    node = next(iter(_nodes(pj, "StreamSelectKey")))
    keys = node["fields"]["schema"]["schema"]["keyColumns"]
    keys.append(dict(keys[0], name="GRAFT_EXTRA_KEY"))
    violations = verify_plan(plan_from_json(pj))
    assert any(v.rule == "key-consistency" for v in violations), violations


# ------------------------------------------- backend classification

def _runtime_ladder(plan, registry, broker):
    """The REAL fallback ladder: the same constructor attempts (and
    exception handling) as engine._build_executor, minus the engine."""
    from ksql_tpu.compiler.jax_expr import DeviceUnsupported
    from ksql_tpu.runtime.device_executor import (
        DeviceExecutor,
        DistributedDeviceExecutor,
    )

    reasons = []
    try:
        DistributedDeviceExecutor(
            plan, broker, registry, batch_size=8192, store_capacity=1 << 17
        )
        return "distributed", reasons
    except DeviceUnsupported as e:
        reasons.append(("distributed", str(e)))
    except Exception as e:  # noqa: BLE001 — engine degrades the same way
        reasons.append(("distributed", f"construction failed: {e}"))
    try:
        DeviceExecutor(
            plan, broker, registry, batch_size=8192, store_capacity=1 << 17
        )
        return "device", reasons
    except DeviceUnsupported as e:
        reasons.append(("device", str(e)))
    except Exception as e:  # noqa: BLE001
        reasons.append(("device", f"construction failed: {e}"))
    return "oracle", reasons


def _agreement_sample(snapshot, per_backend=5):
    """fname/case/qid triples spanning every placement outcome."""
    picked = {"distributed": [], "device": [], "oracle": []}
    for fname, cases in sorted(snapshot.items()):
        for case, qs in sorted(cases.items()):
            for qid, d in sorted(qs.items()):
                bucket = picked[d["backend"]]
                if len(bucket) < per_backend:
                    bucket.append((fname, case, qid))
    return [t for bucket in picked.values() for t in bucket]


def test_backend_snapshot_is_stable():
    """The pinned ahead-of-time placement of the breadth slice.  A diff is
    a compatibility decision: review it, then regenerate with
    ``python scripts/gen_backend_snapshot.py``."""
    with open(SNAPSHOT_PATH) as f:
        want = json.load(f)
    got = json.loads(json.dumps(classify_corpus(BREADTH_FILES)))
    assert got == want, "backend classification drifted — see test docstring"


def test_static_classification_agrees_with_runtime_ladder():
    """Sampled static-vs-runtime agreement across all three outcomes; the
    full-corpus sweep runs under ``-m slow``."""
    from ksql_tpu.runtime.topics import Broker

    with open(SNAPSHOT_PATH) as f:
        snapshot = json.load(f)
    sample = _agreement_sample(snapshot)
    assert len(sample) >= 12  # all three outcomes represented
    registry = FunctionRegistry()
    broker = Broker()
    plans = {
        (fname, case, qid): pj
        for fname, case, qid, pj in _iter_golden_plans(BREADTH_FILES)
    }
    for key in sample:
        plan = plan_from_json(plans[key])
        static = classify_plan(plan, registry, backend="distributed",
                               deep=True)
        rt_backend, rt_reasons = _runtime_ladder(plan, registry, broker)
        assert static.backend == rt_backend, (key, static, rt_reasons)
        assert static.reasons == tuple(rt_reasons), (key, static, rt_reasons)


def test_device_only_classifies_rejected_not_oracle():
    """Under ksql.runtime.backend=device-only the engine raises instead
    of degrading to the oracle, so a plan that fails the device probe
    must classify as rejected — not advertise a backend it can never
    run on."""
    with open(SNAPSHOT_PATH) as f:
        snapshot = json.load(f)
    key = next(
        (fname, case, qid)
        for fname, cases in sorted(snapshot.items())
        for case, qs in sorted(cases.items())
        for qid, d in sorted(qs.items())
        if d["backend"] == "oracle"
        and any(r.startswith("device:") for r in d["reasons"])
    )
    plans = {
        (fname, case, qid): pj
        for fname, case, qid, pj in _iter_golden_plans(BREADTH_FILES)
    }
    plan = plan_from_json(plans[key])
    decision = classify_plan(plan, FunctionRegistry(), backend="device-only",
                             deep=True)
    assert decision.backend == "rejected (device-only)", (key, decision)
    assert any(rung == "device" for rung, _ in decision.reasons)


def test_batched_self_join_reject_honors_capacity_and_device_only(
    monkeypatch,
):
    """The static batched-self-join reject must mirror the runtime
    condition (device_executor: reject iff effective capacity > 1, where
    per-record non-suppress plans run capacity 1) and honor the
    device-only contract (rejected, never an oracle the statement can't
    run on).  The branch is belt-and-braces — real suppress+ss-join plans
    reject earlier in lowering — so the probe is stubbed."""
    import ksql_tpu.analysis.plan_verifier as pv

    pj = _first_plan_with("StreamSink", ["project-filter.json"])
    plan = plan_from_json(pj)  # no join/suppress: per_record_eff is False

    class _SameTopicProbe:
        class _Src:
            topic = "t"

        source = _Src()
        right_source = _Src()
        _needs_seq = False

    monkeypatch.setattr(
        pv, "_device_probe", lambda *a, **k: _SameTopicProbe()
    )
    registry = FunctionRegistry()
    # batched (capacity > 1): the reject fires on both backends
    d = classify_plan(plan, registry, backend="device", capacity=8192)
    assert d.backend == "oracle"
    assert ("device", "batched self-join on device") in d.reasons
    d = classify_plan(plan, registry, backend="device-only", capacity=8192)
    assert d.backend == "rejected (device-only)", d
    # capacity 1: the runtime constructs its device with capacity 1 and
    # never rejects — static must agree
    d = classify_plan(plan, registry, backend="device", capacity=1)
    assert d.backend == "device", d
    assert d.reasons == ()


def test_shallow_tier_only_over_approves():
    """deep=False (the analyze_only structural probe) skips jit wrapping
    and the eval_shape trace, so the only divergence it may show vs
    deep=True is OVER-approval: missing an expression-level
    DeviceUnsupported and reporting a higher rung.  It must never invent
    a reject deep disagrees with, and every reason it reports must be one
    deep reports too."""
    rank = {"rejected (device-only)": 0, "oracle": 0, "device": 1,
            "distributed": 2}
    deep = classify_corpus(BREADTH_FILES, deep=True)
    shallow = classify_corpus(BREADTH_FILES, deep=False)
    diverged = 0
    for fname, cases in deep.items():
        for case, qs in cases.items():
            for qid, d in qs.items():
                s = shallow[fname][case][qid]
                if s == d:
                    continue
                diverged += 1
                key = (fname, case, qid, s, d)
                assert rank[s["backend"]] > rank[d["backend"]], key
                assert set(s["reasons"]) <= set(d["reasons"]), key
    # the tier is meaningfully fast BECAUSE it's nearly as exact: the
    # breadth slice diverges only on its handful of expression-level gaps
    assert diverged <= 12, diverged


@pytest.mark.slow
def test_static_classification_agrees_on_full_corpus():
    from ksql_tpu.runtime.topics import Broker

    registry = FunctionRegistry()
    broker = Broker()
    mismatches = []
    for fname, case, qid, pj in _iter_golden_plans():
        plan = plan_from_json(pj)
        static = classify_plan(plan, registry, backend="distributed",
                               deep=True)
        rt_backend, rt_reasons = _runtime_ladder(plan, registry, broker)
        if static.backend != rt_backend or static.reasons != tuple(rt_reasons):
            mismatches.append(
                (fname, case, qid, static.backend, rt_backend)
            )
    assert not mismatches, mismatches[:10]


# ------------------------------------------- engine integration (EXPLAIN)

def _engine(**overrides):
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine

    props = {"ksql.runtime.backend": "device"}
    props.update(overrides)
    return KsqlEngine(KsqlConfig(props))


def test_explain_statement_surfaces_static_backend():
    e = _engine()
    e.execute_sql(
        "CREATE STREAM A (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='an_a', value_format='JSON');"
    )
    # a transient (sinkless) plan classifies like the transient path runs
    # it — synthetic sink, per-record, single-device rung — and draws no
    # plan-shape violation
    out = e.execute_sql("EXPLAIN SELECT ID, V + 1 AS W FROM A;")
    assert "Backend (static): device" in out[0].message
    assert "plan without sink" not in out[0].message
    assert "Plan violation" not in out[0].message
    # a persistent query's plan classifies to the device it runs on
    r = e.execute_sql("CREATE STREAM A_OUT AS SELECT ID, V + 1 AS W FROM A;")
    out = e.execute_sql(f"EXPLAIN {r[0].query_id};")
    assert "Runtime: device" in out[0].message
    assert "Backend (static): device" in out[0].message


def test_explain_running_query_shows_static_next_to_live():
    e = _engine(**{"ksql.runtime.backend": "oracle"})
    e.execute_sql(
        "CREATE STREAM B (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='an_b', value_format='JSON');"
    )
    r = e.execute_sql("CREATE STREAM B_OUT AS SELECT ID, V + 1 AS W FROM B;")
    out = e.execute_sql(f"EXPLAIN {r[0].query_id};")
    assert "Runtime: oracle" in out[0].message
    # configured-oracle classification agrees with the live placement
    assert "Backend (static): oracle" in out[0].message


def test_explain_memo_invalidates_on_classification_input_change(
    monkeypatch,
):
    """The handle-memoized EXPLAIN decision must recompute when ANY
    classification input changes — not just backend/cadence: a SET on a
    function limit (baked into the deep probe's collect/topk state) or a
    capacity change would otherwise serve a stale decision."""
    import ksql_tpu.analysis as analysis_mod
    from ksql_tpu.analysis import classify_plan as real_classify

    e = _engine(**{"ksql.runtime.backend": "oracle"})
    e.execute_sql(
        "CREATE STREAM M (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='an_m', value_format='JSON');"
    )
    r = e.execute_sql("CREATE STREAM M_OUT AS SELECT ID, V FROM M;")
    qid = r[0].query_id
    calls = []
    monkeypatch.setattr(
        analysis_mod, "classify_plan",
        lambda *a, **k: calls.append(1) or real_classify(*a, **k),
    )
    e.execute_sql(f"EXPLAIN {qid};")
    e.execute_sql(f"EXPLAIN {qid};")
    assert len(calls) == 1  # unchanged inputs: memo hit
    e.session_properties["ksql.functions.collect_list.limit"] = "7"
    e.execute_sql(f"EXPLAIN {qid};")
    assert len(calls) == 2  # limit change invalidates
    e.execute_sql(f"EXPLAIN {qid};")
    assert len(calls) == 2  # and the new key memoizes again


def test_verifier_hook_logs_and_strict_rejects():
    import ksql_tpu.common.config as cfg
    from ksql_tpu.common.errors import KsqlException

    pj = _first_plan_with("WindowExpression", ["tumbling-windows.json"])
    for w in _nodes(pj, "WindowExpression"):
        w["fields"]["size_ms"] = -5
    broken = plan_from_json(pj)

    e = _engine(**{"ksql.runtime.backend": "oracle"})
    e._verify_plan_static("Q_TEST", broken)
    assert any(w.startswith("plan.verify:Q_TEST")
               for w, _ in e.processing_log)

    e.session_properties[cfg.ANALYSIS_VERIFY_STRICT] = True
    with pytest.raises(KsqlException):
        e._verify_plan_static("Q_TEST", broken)

    # the knob: verification off -> strict cannot fire either
    e.session_properties[cfg.ANALYSIS_VERIFY_PLANS] = False
    e._verify_plan_static("Q_TEST", broken)


def test_strict_rejection_leaves_no_orphaned_metadata(monkeypatch):
    """A strict-mode rejection must fire BEFORE the sink source / topic /
    SR subjects register — resubmitting the corrected statement must not
    hit 'source already exists'."""
    import ksql_tpu.analysis as analysis_mod
    import ksql_tpu.common.config as cfg
    from ksql_tpu.analysis import PlanViolation
    from ksql_tpu.common.errors import KsqlException

    e = _engine(**{"ksql.runtime.backend": "oracle"})
    e.execute_sql(
        "CREATE STREAM SRC0 (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='orph_src', value_format='JSON');"
    )
    e.session_properties[cfg.ANALYSIS_VERIFY_STRICT] = True
    monkeypatch.setattr(
        analysis_mod, "verify_plan",
        lambda plan: [PlanViolation("ctx", "StreamSink", "serde-invariant",
                                    "injected violation")],
    )
    with pytest.raises(KsqlException, match="static verification"):
        e.execute_sql("CREATE STREAM OUT0 AS SELECT ID FROM SRC0;")
    assert e.metastore.get_source("OUT0") is None
    monkeypatch.undo()
    # corrected resubmission succeeds without OR REPLACE
    e.session_properties[cfg.ANALYSIS_VERIFY_STRICT] = False
    r = e.execute_sql("CREATE STREAM OUT0 AS SELECT ID FROM SRC0;")
    assert r[0].query_id
