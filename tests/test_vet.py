"""kube-vet + locksmith tests.

Every rule is exercised against a known-bad fixture (including a
reconstruction of the literal r11 donation-aliasing bug from
solver/mesh_exec.py pre-fix, and the PR 1 f-string form that muted 13
test modules) and against the fixed form; waiver syntax is honored and
reason-required; locksmith detects an injected A->B / B->A inversion
and stays quiet on a clean ordering. test_tree_is_vet_clean is the
tier-1 gate: the committed tree must vet to zero active violations.
"""

import os
import subprocess
import sys
import textwrap
import threading

from kubernetes_tpu.analysis import run_vet
from kubernetes_tpu.util import locksmith

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _vet_source(tmp_path, source, rel="kubernetes_tpu/mod.py", rules=None):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    active, waived = run_vet(paths=[str(path)], rule_ids=rules,
                             root=str(tmp_path))
    return active, waived


def _rules_of(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

# the literal r11 shape: a jitted delta scatter donating its base buffer
# unconditionally — on the CPU backend a device_put-established base may
# alias the cached host numpy array, and donating it frees numpy-owned
# memory (observed live as malloc() heap corruption killing solverd)
R11_BAD = """
    import jax
    import numpy as np

    def _scatter_fn(sharding):
        def f(base, rows, vals):
            return base.at[rows].set(vals)
        return jax.jit(f, out_shardings=sharding, donate_argnums=(0,))

    def apply_delta(cache, name, sharding, rows, vals):
        src, dev = cache[name]          # dev may be device_put(src): aliased
        return _scatter_fn(sharding)(dev, rows, vals)
"""

R11_FIXED = """
    import jax
    import numpy as np

    def _scatter_fn(sharding, donate):
        def f(base, rows, vals):
            return base.at[rows].set(vals)
        return jax.jit(f, out_shardings=sharding,
                       donate_argnums=(0,) if donate else ())

    def apply_delta(cache, name, sharding, rows, vals):
        src, dev, xla_owned = cache[name]
        return _scatter_fn(sharding, donate=xla_owned)(dev, rows, vals)
"""


class TestDonationSafety:
    def test_r11_unconditional_donation_flagged(self, tmp_path):
        active, _ = _vet_source(tmp_path, R11_BAD,
                                rules=["donation-safety"])
        assert _rules_of(active) == ["donation-safety"]
        assert "donate_argnums" in active[0].message

    def test_fixed_guarded_form_clean(self, tmp_path):
        active, _ = _vet_source(tmp_path, R11_FIXED,
                                rules=["donation-safety"])
        assert active == []

    def test_donate_true_literal_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path, "fn = compile_program(mesh, donate=True)\n",
            rules=["donation-safety"])
        assert _rules_of(active) == ["donation-safety"]

    def test_donate_false_and_empty_clean(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import jax\n"
            "f1 = jax.jit(lambda x: x, donate_argnums=())\n"
            "f2 = compile_program(mesh, donate=False)\n",
            rules=["donation-safety"])
        assert active == []

    def test_opaque_provenance_needs_waiver(self, tmp_path):
        # rec[2] WAS the xla_owned slot, but a subscript proves nothing
        active, _ = _vet_source(
            tmp_path, "f = scatter(sh, donate=rec[2])\n",
            rules=["donation-safety"])
        assert _rules_of(active) == ["donation-safety"]

    def test_committed_mesh_exec_is_guarded(self):
        active, _ = run_vet(
            paths=[os.path.join(REPO, "kubernetes_tpu/solver/mesh_exec.py"),
                   os.path.join(REPO, "kubernetes_tpu/parallel/mesh.py")],
            rule_ids=["donation-safety"], root=REPO)
        assert active == []


# ---------------------------------------------------------------------------
# thread-discipline
# ---------------------------------------------------------------------------

class TestThreadDiscipline:
    def test_unjoined_nondaemon_thread_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import threading\n"
            "def start():\n"
            "    t = threading.Thread(target=print)\n"
            "    t.start()\n",
            rules=["thread-discipline"])
        assert _rules_of(active) == ["thread-discipline"]

    def test_daemon_thread_clean(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import threading\n"
            "def start():\n"
            "    threading.Thread(target=print, daemon=True).start()\n",
            rules=["thread-discipline"])
        assert active == []

    def test_joined_thread_clean(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import threading\n"
            "class S:\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=print)\n"
            "        self._thread.start()\n"
            "    def stop(self):\n"
            "        self._thread.join()\n",
            rules=["thread-discipline"])
        assert active == []

    def test_loop_joined_collection_clean(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import threading\n"
            "def run(n):\n"
            "    ts = [threading.Thread(target=print) for _ in range(n)]\n"
            "    for t in ts:\n"
            "        t.start()\n"
            "    for t in ts:\n"
            "        t.join()\n",
            rules=["thread-discipline"])
        assert active == []

    def test_unbounded_queue_flagged_bounded_clean(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import queue\n"
            "import threading\n"
            "bad = queue.Queue()\n"
            "also_bad = queue.Queue(maxsize=0)\n"
            "ok = queue.Queue(maxsize=64)\n",
            rules=["thread-discipline"])
        assert _rules_of(active) == ["thread-discipline",
                                     "thread-discipline"]

    def test_unbounded_deque_in_threaded_module_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import threading\n"
            "from collections import deque\n"
            "bad = deque()\n"
            "ok = deque(maxlen=128)\n",
            rules=["thread-discipline"])
        assert _rules_of(active) == ["thread-discipline"]

    def test_deque_without_threads_is_fine(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "from collections import deque\n"
            "fine = deque()\n",
            rules=["thread-discipline"])
        assert active == []


# ---------------------------------------------------------------------------
# clone-mutation
# ---------------------------------------------------------------------------

class TestCloneMutation:
    def test_mutating_clone_source_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "from kubernetes_tpu.runtime.clone import deep_clone\n"
            "def assume(pod, modeler):\n"
            "    cl = deep_clone(pod)\n"
            "    pod.status.phase = 'Assumed'\n"   # mutates the SHARED obj
            "    modeler.assume_pod(cl)\n",
            rules=["clone-mutation"])
        assert _rules_of(active) == ["clone-mutation"]
        assert "deep_clone" in active[0].message

    def test_mutating_the_clone_is_fine(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "from kubernetes_tpu.runtime.clone import deep_clone\n"
            "def assume(pod, modeler):\n"
            "    cl = deep_clone(pod)\n"
            "    cl.status.phase = 'Assumed'\n"
            "    cl.metadata.annotations.update({'a': 'b'})\n"
            "    modeler.assume_pod(cl)\n",
            rules=["clone-mutation"])
        assert active == []

    def test_mutator_method_on_source_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "from kubernetes_tpu.runtime.clone import deep_clone\n"
            "def assume(pod):\n"
            "    cl = deep_clone(pod)\n"
            "    pod.metadata.labels.update({'x': 'y'})\n",
            rules=["clone-mutation"])
        assert _rules_of(active) == ["clone-mutation"]

    def test_atomic_class_with_mutator_flagged(self, tmp_path):
        # a mutable class snuck into _ATOMIC: shared verbatim between
        # clone and original, so any mutator corrupts both views
        root = tmp_path
        clone = root / "kubernetes_tpu/runtime/clone.py"
        clone.parent.mkdir(parents=True)
        clone.write_text(textwrap.dedent("""
            from kubernetes_tpu.api.quantity import Quantity
            _ATOMIC = frozenset({str, int, Quantity})
        """))
        q = root / "kubernetes_tpu/api/quantity.py"
        q.parent.mkdir(parents=True)
        q.write_text(textwrap.dedent("""
            class Quantity:
                def __init__(self, v):
                    self.value = v
                def scale(self, k):
                    self.value = self.value * k   # in-place mutator
        """))
        active, _ = run_vet(paths=[str(clone), str(q)],
                            rule_ids=["clone-mutation"], root=str(root))
        assert _rules_of(active) == ["clone-mutation"]
        assert "Quantity.scale" in active[0].message

    def test_committed_quantity_is_immutable(self):
        active, _ = run_vet(
            paths=[os.path.join(REPO, "kubernetes_tpu/runtime/clone.py"),
                   os.path.join(REPO, "kubernetes_tpu/api/quantity.py")],
            rule_ids=["clone-mutation"], root=REPO)
        assert active == []

    def test_wholesale_dict_copy_in_clone_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "def deep_clone(obj):\n"
            "    new = object.__new__(obj.__class__)\n"
            "    new.__dict__.update(obj.__dict__)\n"
            "    return new\n",
            rel="kubernetes_tpu/runtime/clone.py",
            rules=["clone-mutation"])
        assert _rules_of(active) == ["clone-mutation"]
        assert "__dict__" in active[0].message


# ---------------------------------------------------------------------------
# metrics-sync
# ---------------------------------------------------------------------------

class TestMetricsSync:
    def _tree(self, tmp_path, scrape_name):
        reg = tmp_path / "kubernetes_tpu/util/metrics.py"
        reg.parent.mkdir(parents=True)
        reg.write_text(textwrap.dedent("""
            def build(reg):
                c = reg.counter("solverd_frobs_total", "frobs")
                h = reg.histogram("wave_frob_seconds", "frob time")
                return c, h
        """))
        churn = tmp_path / "hack/churn_mp.py"
        churn.parent.mkdir(parents=True)
        churn.write_text(
            f'def scrape(vals):\n'
            f'    return vals.get("{scrape_name}", 0.0)\n')
        return [str(reg), str(churn)]

    def test_renamed_series_flagged(self, tmp_path):
        paths = self._tree(tmp_path, "solverd_frob_count_total")
        active, _ = run_vet(paths=paths, rule_ids=["metrics-sync"],
                            root=str(tmp_path))
        assert _rules_of(active) == ["metrics-sync"]
        assert "solverd_frob_count_total" in active[0].message

    def test_registered_series_clean(self, tmp_path):
        paths = self._tree(tmp_path, "solverd_frobs_total")
        active, _ = run_vet(paths=paths, rule_ids=["metrics-sync"],
                            root=str(tmp_path))
        assert active == []

    def test_histogram_derived_series_resolve(self, tmp_path):
        paths = self._tree(tmp_path, "wave_frob_seconds_bucket")
        active, _ = run_vet(paths=paths, rule_ids=["metrics-sync"],
                            root=str(tmp_path))
        assert active == []

    def test_record_keys_are_not_series_refs(self, tmp_path):
        # short record keys ('transfer_bytes') must not bind to the rule
        paths = self._tree(tmp_path, "solverd_frobs_total")
        churn = tmp_path / "hack/churn_mp.py"
        churn.write_text(churn.read_text()
                         + 'K = {"transfer_bytes": 1, "solve_p50_ms": 2}\n')
        active, _ = run_vet(paths=paths, rule_ids=["metrics-sync"],
                            root=str(tmp_path))
        assert active == []

    def test_committed_gates_resolve(self):
        # the real contract: churn scrape + SLO rules + perfgate vs the
        # real registry universe
        active, _ = run_vet(rule_ids=["metrics-sync"], root=REPO)
        assert active == []


# ---------------------------------------------------------------------------
# unused
# ---------------------------------------------------------------------------

class TestUnused:
    def test_unused_import_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import os\n"
            "import json\n"
            "print(os.getpid())\n",
            rules=["unused"])
        assert _rules_of(active) == ["unused"]
        assert "json" in active[0].message

    def test_string_annotation_counts_as_use(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "from collections import deque\n"
            "def f(q: \"deque\"):\n"
            "    return q\n",
            rules=["unused"])
        assert active == []

    def test_dead_private_flagged_public_exempt(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "_DEAD = 42\n"
            "PUBLIC = 43\n"
            "def _dead_fn():\n"
            "    return 1\n",
            rules=["unused"])
        assert sorted(v.message.split("'")[1] for v in active) == \
            ["_DEAD", "_dead_fn"]

    def test_cross_module_private_import_counts(self, tmp_path):
        a = tmp_path / "kubernetes_tpu/a.py"
        a.parent.mkdir(parents=True)
        a.write_text("_HELPER = 1\n")
        b = tmp_path / "kubernetes_tpu/b.py"
        b.write_text("from kubernetes_tpu.a import _HELPER\n"
                     "print(_HELPER)\n")
        active, _ = run_vet(paths=[str(a), str(b)], rule_ids=["unused"],
                            root=str(tmp_path))
        assert active == []

    def test_reexport_through_module_counts(self, tmp_path):
        a = tmp_path / "kubernetes_tpu/a.py"
        a.parent.mkdir(parents=True)
        a.write_text("from os import sep\n")     # unused here...
        b = tmp_path / "kubernetes_tpu/b.py"
        b.write_text("from kubernetes_tpu.a import sep\nprint(sep)\n")
        active, _ = run_vet(paths=[str(a), str(b)], rule_ids=["unused"],
                            root=str(tmp_path))
        assert active == []                       # ...but re-exported


# ---------------------------------------------------------------------------
# waiver semantics
# ---------------------------------------------------------------------------

class TestWaivers:
    def test_waiver_silences_exactly_its_rule(self, tmp_path):
        active, waived = _vet_source(
            tmp_path,
            "import queue\n"
            "import threading\n"
            "# ktpu-vet: ok thread-discipline — producer is rate-limited"
            " upstream\n"
            "q = queue.Queue()\n",
            rules=["thread-discipline"])
        assert active == []
        assert len(waived) == 1
        assert waived[0].waiver_reason.startswith("producer is")

    def test_waiver_on_same_line(self, tmp_path):
        active, waived = _vet_source(
            tmp_path,
            "import queue\n"
            "import threading\n"
            "q = queue.Queue()  # ktpu-vet: ok thread-discipline — "
            "drained synchronously\n",
            rules=["thread-discipline"])
        assert active == []
        assert len(waived) == 1

    def test_waiver_requires_reason(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import queue\n"
            "import threading\n"
            "q = queue.Queue()  # ktpu-vet: ok thread-discipline\n")
        assert "waiver" in _rules_of(active)
        # and the undischarged violation stays active too
        assert "thread-discipline" in _rules_of(active)

    def test_waiver_unknown_rule_flagged(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "x = 1  # ktpu-vet: ok no-such-rule — because\n")
        assert "waiver" in _rules_of(active)
        assert "unknown rule" in next(
            v for v in active if v.rule == "waiver").message

    def test_waiver_does_not_cover_other_rules(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            "import threading\n"
            "def start():\n"
            "    # ktpu-vet: ok unused — wrong rule named\n"
            "    t = threading.Thread(target=print)\n"
            "    t.start()\n",
            rules=["thread-discipline"])
        assert _rules_of(active) == ["thread-discipline"]

    def test_stale_waiver_flagged_on_full_run(self, tmp_path):
        # the waived violation was fixed but the comment lingered: a
        # full-rule-set run flags it so silencing can never outlive its
        # finding (rule-subset runs skip the check — a waiver for an
        # unselected rule is legitimately idle)
        src = ("import queue\n"
               "import threading\n"
               "# ktpu-vet: ok thread-discipline — bounded upstream\n"
               "q = queue.Queue(maxsize=8)\n"
               "print(q, threading)\n")
        active, _ = _vet_source(tmp_path, src)
        assert [v.rule for v in active] == ["waiver"]
        assert "matches no violation" in active[0].message
        active, _ = _vet_source(tmp_path, src, rules=["unused"])
        assert active == []

    def test_waiver_pseudo_rule_id_is_selectable(self, tmp_path):
        # run_vet(rule_ids=['waiver']) must run the hygiene check, not
        # crash on the unregistered pseudo-rule id
        active, _ = _vet_source(
            tmp_path, "x = 1  # ktpu-vet: ok unused\n", rules=["waiver"])
        assert _rules_of(active) == ["waiver"]

    def test_waiver_in_docstring_is_not_a_waiver(self, tmp_path):
        active, _ = _vet_source(
            tmp_path,
            '"""Docs: use `# ktpu-vet: ok unused — reason` to waive."""\n'
            "import queue\n"
            "import threading\n"
            "q = queue.Queue()\n",
            rules=["thread-discipline"])
        assert _rules_of(active) == ["thread-discipline"]


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------

class TestCli:
    def _run(self, *args):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "hack/vet.py"), *args],
            capture_output=True, text=True, env=env)

    def test_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import json\n")
        good = tmp_path / "good.py"
        good.write_text("import json\nprint(json.dumps({}))\n")
        r = self._run(str(bad))
        assert r.returncode == 1, r.stdout + r.stderr
        assert "[unused]" in r.stdout
        r = self._run(str(good))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_cli_flags_r11_donation_fixture(self, tmp_path):
        bad = tmp_path / "r11.py"
        bad.write_text(textwrap.dedent(R11_BAD))
        r = self._run("--rules", "donation-safety", str(bad))
        assert r.returncode == 1, r.stdout + r.stderr
        assert "[donation-safety]" in r.stdout

    def test_list_rules(self):
        r = self._run("--list-rules")
        assert r.returncode == 0
        for rid in ("donation-safety", "clone-mutation",
                    "thread-discipline", "metrics-sync", "unused"):
            assert rid in r.stdout


# ---------------------------------------------------------------------------
# locksmith — the runtime half
# ---------------------------------------------------------------------------

class TestLocksmith:
    def setup_method(self):
        self._before = {r["locks"][0] for r in locksmith.reports()}

    def test_injected_inversion_detected_with_both_stacks(self):
        a = locksmith.wrap("test-lock-A")
        b = locksmith.wrap("test-lock-B")
        done = []

        def t1():
            with a:
                with b:
                    done.append(1)

        def t2():
            with b:
                with a:
                    done.append(2)

        # sequential, so the inversion is recorded without the hang —
        # exactly the case locksmith exists for
        th1 = threading.Thread(target=t1)
        th1.start()
        th1.join()
        th2 = threading.Thread(target=t2)
        th2.start()
        th2.join()
        assert done == [1, 2]
        reps = [r for r in locksmith.reports()
                if "test-lock-A" in r["locks"]
                or "test-lock-B" in r["locks"]]
        assert len(reps) == 1, locksmith.reports()
        rep = reps[0]
        assert set(rep["locks"][:-1]) >= {"test-lock-A", "test-lock-B"}
        assert len(rep["edges"]) == 2
        for e in rep["edges"]:          # BOTH stacks captured
            assert e["stack"], rep
        text = locksmith.format_report(rep)
        assert "test-lock-A" in text and "test-lock-B" in text
        # injected on purpose, not a finding — but clear() would also
        # wipe every edge earlier suites recorded into the session-wide
        # KTPU_LOCK_EDGES aggregate, so drop only these two locks
        locksmith.forget_named("test-lock-A", "test-lock-B")

    def test_clean_ordering_passes(self):
        a = locksmith.wrap("ordered-A")
        b = locksmith.wrap("ordered-B")

        def worker():
            for _ in range(50):
                with a:
                    with b:
                        pass

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not [r for r in locksmith.reports()
                    if "ordered-A" in r["locks"]]

    def test_rlock_reentry_is_not_a_cycle(self):
        r = locksmith.wrap("reentrant", rlock=True)
        with r:
            with r:
                pass
        assert not [x for x in locksmith.reports()
                    if "reentrant" in x["locks"]]

    def test_condition_wait_releases_chain(self):
        # Condition.wait() fully releases its (tracked) RLock: another
        # lock acquired while waiting must NOT edge against it
        r = locksmith.TrackedRLock("cond-lock")
        cond = threading.Condition(r)
        other = locksmith.wrap("cond-other")
        hit = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                hit.append(1)

        t = threading.Thread(target=waiter)
        t.start()
        # let the waiter block, then take the other lock and notify
        import time
        time.sleep(0.1)
        with other:
            with cond:
                cond.notify()
        t.join()
        assert hit == [1]
        assert not [x for x in locksmith.reports()
                    if "cond-other" in x["locks"]
                    and "cond-lock" in x["locks"]]

    def test_arm_disarm_roundtrip(self):
        was_armed = locksmith.armed()
        try:
            locksmith.arm()
            assert locksmith.armed()
            lk = threading.Lock()
            assert isinstance(lk, locksmith.TrackedLock)
            with lk:
                pass
        finally:
            locksmith.disarm()
            assert threading.Lock is locksmith._REAL_LOCK
            if was_armed:       # --race mode: leave it as we found it
                locksmith.arm()


# ---------------------------------------------------------------------------
# the tier-1 gate: the committed tree must be vet-clean
# ---------------------------------------------------------------------------

def test_tree_is_vet_clean():
    active, waived = run_vet(root=REPO)
    msgs = "\n".join(
        f"{v.path}:{v.line}: [{v.rule}] {v.message}" for v in active)
    assert active == [], f"kube-vet violations in the tree:\n{msgs}"
    # every surviving waiver carries a rule id + reason by construction
    # (engine enforces it); keep the count visible so review notices growth
    assert len(waived) < 20, [v.path for v in waived]
