"""fedlint (fedml_tpu.analysis): per-rule firing fixtures (positive +
non-firing negative), waiver syntax, report schema, config parsing, and
the tier-1 zero-findings gate over the real package run in-process."""

import dataclasses
import importlib.util
import io
import json
import textwrap
from pathlib import Path

import pytest

from fedml_tpu.analysis import (
    FedlintConfig,
    load_config,
    make_rules,
    render_json,
    run_analysis,
)
from fedml_tpu.analysis.config import _parse_fallback
from fedml_tpu.analysis.report import live_findings

REPO = Path(__file__).parent.parent


def lint(tmp_path, sources, select=None, config=None):
    """Write fixture modules, run the selected rules, return (live, all,
    waivers)."""
    for name, src in sources.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    cfg = config or FedlintConfig()
    if select:
        cfg = dataclasses.replace(cfg, select=tuple(select))
    findings, waivers, _ = run_analysis(
        [str(tmp_path)], make_rules(cfg), exclude=cfg.exclude,
        root=str(tmp_path),
    )
    return live_findings(findings), findings, waivers


# -- rule: guarded-by --------------------------------------------------------


GUARDED_SRC = """
    import threading

    class Tally:
        def __init__(self):
            self._acc = {}  # guarded-by: _lock
            self._lock = threading.Lock()

        def bad(self):
            self._acc["k"] = 1          # unguarded: fires

        def good(self):
            with self._lock:
                self._acc["k"] = 1      # guarded: clean

        def helper(self):  # lock-held: _lock
            return len(self._acc)       # callee side of caller-holds-lock

        def deferred(self):
            with self._lock:
                def cb():
                    return self._acc    # closure runs later, lock NOT held
                return cb
    """


def test_guarded_by_fires_and_negatives(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": GUARDED_SRC},
                      select=["guarded-by"])
    lines = sorted(f.line for f in live)
    assert all(f.rule == "guarded-by" for f in live)
    # exactly the unguarded touch and the deferred-closure touch fire;
    # the with-block, the lock-held method, and __init__ stay clean
    assert len(live) == 2
    src = (tmp_path / "m.py").read_text().splitlines()
    assert 'self._acc["k"] = 1          # unguarded' in src[lines[0] - 1]
    assert "closure runs later" in src[lines[1] - 1]


def test_guarded_by_inherits_across_files(tmp_path):
    live, _, _ = lint(tmp_path, {
        "base.py": """
            import threading
            class Base:
                def __init__(self):
                    self._state = []  # guarded-by: _lock
                    self._lock = threading.Lock()
                def tally(self):  # lock-held: _lock
                    return len(self._state)
            """,
        "sub.py": """
            from base import Base
            class Sub(Base):
                def bad(self):
                    self._state.append(1)   # base-declared guard: fires
                def tally(self):
                    return 0                # override inherits lock-held
            """,
    }, select=["guarded-by"])
    assert [f.path for f in live] == ["sub.py"]
    assert "guarded by self._lock" in live[0].message
    assert "Base" in live[0].message


def test_guarded_by_checks_colliding_class_names(tmp_path):
    """A class whose simple name collides with one in an earlier file must
    still be walked — a collision can never exempt it from the gate."""
    live, _, _ = lint(tmp_path, {
        "a.py": """
            class Widget:
                def ok(self):
                    return 1
            """,
        "b.py": """
            import threading
            class Widget:
                def __init__(self):
                    self._q = []  # guarded-by: _lock
                    self._lock = threading.Lock()
                def bad(self):
                    self._q.append(1)
            """,
    }, select=["guarded-by"])
    assert [f.path for f in live] == ["b.py"]


# -- rule: overwrite-after-super ---------------------------------------------


def test_overwrite_after_super_fires_and_factory_is_clean(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        class Tally:
            pass

        class Base:
            def __init__(self):
                self.agg = Tally()

        class Overwriter(Base):
            def __init__(self):
                super().__init__()
                self.agg = Tally()      # construct-then-overwrite: fires

        class Hoister(Base):
            def __init__(self):
                self.cfg = object()     # hoisted config: clean
                super().__init__()

        class Coercer(Base):
            def __init__(self):
                super().__init__()
                self.n = int(3)         # builtin coercion: not construction
        """}, select=["overwrite-after-super"])
    assert len(live) == 1
    assert live[0].rule == "overwrite-after-super"
    assert "Base.__init__" in live[0].message


# -- rule: wire-contract -----------------------------------------------------


def test_wire_contract_fires_and_negatives(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        class Msg:
            MSG_ARG_KEY_GOOD = "good_key"
            MSG_ARG_KEY_DEAD = "dead_key"       # never written: fires
            MSG_ARG_KEY_BLIND = "blind_key"     # never read: fires

        def send(msg):
            msg.add_params(Msg.MSG_ARG_KEY_GOOD, 1)
            msg.add_params(Msg.MSG_ARG_KEY_BLIND, 2)
            msg.add_params("adhoc_key", 3)      # raw add_params key: fires

        def recv(msg):
            a = msg.get(Msg.MSG_ARG_KEY_GOOD)
            b = msg.get(Msg.MSG_ARG_KEY_DEAD)
            return a, b, "good_key"             # duplicate literal: fires
        """}, select=["wire-contract"])
    msgs = sorted(f.message for f in live)
    assert len(live) == 4
    assert any("never written" in m and "MSG_ARG_KEY_DEAD" in m for m in msgs)
    assert any("never read" in m and "MSG_ARG_KEY_BLIND" in m for m in msgs)
    assert any("ad-hoc wire key 'adhoc_key'" in m for m in msgs)
    assert any("raw string 'good_key' duplicates" in m for m in msgs)


def test_wire_contract_alias_constants_are_clean(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        class Message:
            MSG_ARG_KEY_X = "x_key"

        class MyMessage:
            MSG_ARG_KEY_X = Message.MSG_ARG_KEY_X   # alias, not a dup

        def roundtrip(msg):
            msg.add_params(MyMessage.MSG_ARG_KEY_X, 1)
            return msg.get(Message.MSG_ARG_KEY_X)
        """}, select=["wire-contract"])
    assert live == []


# -- rule: traced-purity -----------------------------------------------------


def test_traced_purity_fires_and_negatives(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        import time
        import jax

        @jax.jit
        def decorated(x):
            t = time.time()             # host call in traced body: fires
            return x + t

        def by_name(x):
            print(x)                    # traced via jax.jit(by_name): fires
            return x

        stepped = jax.jit(by_name)

        def host_side(x):
            time.time()                 # never lowered: clean
            print(x)
            return x
        """}, select=["traced-purity"])
    assert len(live) == 2
    assert all(f.rule == "traced-purity" for f in live)
    assert any("time.time()" in f.message and "`decorated`" in f.message
               for f in live)
    assert any("print()" in f.message and "`by_name`" in f.message
               for f in live)


def test_traced_purity_method_handle_lowered_by_reference(tmp_path):
    # the packed-sharded engine idiom: a BOUND METHOD handle passed to a
    # lowering call (displib.lower(self._packed_agg_impl, ...)) — the
    # scanner must record the terminal attribute name so the method body
    # is checked like any other traced program
    live, _, _ = lint(tmp_path, {"m.py": """
        import time

        from fedml_tpu.parallel import dispatch as displib

        class Engine:
            def _packed_agg_impl(self, x):
                t = time.time()         # host call in traced body: fires
                return x + t

            def _host_helper(self, x):
                time.time()             # never lowered: clean
                return x

            def build(self):
                self._fn = displib.lower(
                    self._packed_agg_impl,
                    mesh=None, in_specs=(), out_specs=(),
                )
        """}, select=["traced-purity"])
    assert len(live) == 1 and live[0].rule == "traced-purity"
    assert "time.time()" in live[0].message
    assert "_packed_agg_impl" in live[0].message


def test_traced_purity_method_handles_lowered_as_rows_of_a_table(tmp_path):
    # the engine's table of programs (FedSim._build_programs): the handles
    # sit in rows and one lowering call takes the loop's variable, so what
    # the rows hold is scanned, a row's handle named apart from it included
    live, _, _ = lint(tmp_path, {"m.py": """
        import time

        from fedml_tpu.parallel import dispatch as displib

        class Engine:
            def _round_impl(self, x):
                return x + time.time()  # in a row: fires

            def _train_impl(self, x):
                print(x)                # in a tuple a row unpacks: fires
                return x

            def _host_helper(self, x):
                time.time()             # in no row: clean
                return x

            def build(self):
                train = ("_train_fn", self._train_impl)
                rows = [("_round_fn", self._round_impl, ()), (*train, ())]
                for name, impl, specs in rows:
                    setattr(self, name, displib.lower(
                        impl, mesh=None, in_specs=specs, out_specs=()))
                self._host_helper(0)
        """}, select=["traced-purity"])
    assert sorted(f.message.split("`")[1] for f in live) == ["_round_impl", "_train_impl"]


def test_traced_purity_module_wide_bans(tmp_path):
    # banned-module-calls: np.random.* is illegal at ANY scope in modules
    # under the configured prefix (the population subsystem's replay-
    # determinism contract), while other modules keep the traced-only rule
    cfg = dataclasses.replace(
        FedlintConfig(),
        banned_module_calls=("pkg/population/:np.random.*",),
    )
    src_pop = """
        import numpy as np

        def draw(n):
            return np.random.rand(n)        # module-wide ban: fires

        SEEDED = np.random.RandomState(0)   # module scope: fires
        """
    src_other = """
        import numpy as np

        def draw(n):
            return np.random.rand(n)        # not under the prefix: clean
        """
    live, _, _ = lint(tmp_path, {
        "pkg/population/model.py": src_pop,
        "pkg/other.py": src_other,
    }, select=["traced-purity"], config=cfg)
    assert len(live) == 2, [(f.path, f.line) for f in live]
    assert all(f.path == "pkg/population/model.py" for f in live)
    assert all("banned module-wide" in f.message for f in live)
    # a justified waiver suppresses (but keeps) the finding, as usual
    waived_src = src_pop.replace(
        "SEEDED = np.random.RandomState(0)   # module scope: fires",
        "# fedlint: disable=traced-purity -- the one seeded constructor\n"
        "        SEEDED = np.random.RandomState(0)",
    )
    live2, all2, _ = lint(tmp_path, {
        "pkg/population/model.py": waived_src,
    }, select=["traced-purity"], config=cfg)
    assert len(live2) == 1 and live2[0].line == 5
    assert any(f.waived for f in all2)
    # a malformed entry fails loudly at rule construction
    from fedml_tpu.analysis import make_rules

    with pytest.raises(ValueError, match="banned-module-calls"):
        make_rules(dataclasses.replace(
            FedlintConfig(), banned_module_calls=("no-colon-pattern",),
            select=("traced-purity",),
        ))


# -- rule: metric-keys -------------------------------------------------------


def test_metric_keys_fires_and_negatives(tmp_path):
    cfg = dataclasses.replace(FedlintConfig(),
                              metric_modules=("obs/metrics.py",))
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "METRICS.md").write_text("| `Comm/Bytes` | ... |\n")
    live, _, _ = lint(tmp_path, {
        "obs/metrics.py": """
            COMM_BYTES = "Comm/Bytes"       # defining module: clean
            """,
        "user.py": """
            from obs import metrics

            def record(log):
                log(metrics.COMM_BYTES, 1)          # constant: clean
                log("Comm/Bytes", 2)                # ad-hoc literal: fires
                return "the Async/* totals"         # prose w/ space: clean
            """,
    }, select=["metric-keys"], config=cfg)
    assert len(live) == 1
    assert live[0].path == "user.py"
    assert "'Comm/Bytes'" in live[0].message


def test_metric_keys_dead_metric_checks(tmp_path):
    """The dead-metric arm: a canonical key defined but never emitted, or
    emitted but never consumed by a reader tool or docs table, is a
    finding — reader references and docs mentions are both negatives."""
    cfg = dataclasses.replace(
        FedlintConfig(),
        metric_modules=("obs/metrics.py",),
        metric_reader_modules=("tools/report.py",),
        metric_doc_paths=("docs",),
    )
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "METRICS.md").write_text("| `Comm/Used` | docs |\n")
    sources = {
        "obs/metrics.py": """
            COMM_USED = "Comm/Used"          # emitted + in docs: clean
            COMM_BY_TOOL = "Comm/ByTool"     # emitted + reader refs: clean
            COMM_GHOST = "Comm/Ghost"        # never emitted: fires
            COMM_UNREAD = "Comm/Unread"      # emitted, no consumer: fires
            """,
        "user.py": """
            from obs import metrics

            def record(log):
                log(metrics.COMM_USED, 1)
                log(metrics.COMM_BY_TOOL, 2)
                log(metrics.COMM_UNREAD, 3)
            """,
        "tools/report.py": """
            from obs import metrics

            def render(rec):
                return rec[metrics.COMM_BY_TOOL]
            """,
    }
    live, _, _ = lint(tmp_path, sources, select=["metric-keys"], config=cfg)
    assert [f.path for f in live] == ["obs/metrics.py"] * 2
    msgs = sorted(f.message for f in live)
    assert "COMM_GHOST" in msgs[0] and "never emitted" in msgs[0]
    assert "COMM_UNREAD" in msgs[1] and "never read" in msgs[1]
    # a reader-module reference to the unread key clears it
    sources["tools/report.py"] = sources["tools/report.py"].replace(
        "metrics.COMM_BY_TOOL", "metrics.COMM_UNREAD")
    live2, _, _ = lint(tmp_path, sources, select=["metric-keys"], config=cfg)
    msgs2 = [f.message for f in live2]
    assert len(live2) == 2  # BY_TOOL lost its reader -> unread; GHOST stays
    assert any("COMM_GHOST" in m for m in msgs2)
    assert any("COMM_BY_TOOL" in m and "never read" in m for m in msgs2)


# -- rule: lock-order --------------------------------------------------------


LOCK_CYCLE_SRC = """
    import threading

    class Mgr:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def fold(self):
            with self._a:
                with self._b:       # a -> b
                    return 1

        def close(self):
            with self._b:
                with self._a:       # b -> a: the seeded deadlock
                    return 2
    """


def test_lock_order_cycle_fires_with_full_path(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": LOCK_CYCLE_SRC},
                      select=["lock-order"])
    assert len(live) == 1
    f = live[0]
    assert f.rule == "lock-order"
    # the finding names the FULL cycle with both acquisition sites
    assert "lock-order cycle Mgr._a -> Mgr._b -> Mgr._a" in f.message
    assert "Mgr.fold" in f.message and "Mgr.close" in f.message


def test_lock_order_consistent_order_is_clean(tmp_path):
    src = LOCK_CYCLE_SRC.replace(
        "with self._b:\n                with self._a:",
        "with self._a:\n                with self._b:")
    live, _, _ = lint(tmp_path, {"m.py": src}, select=["lock-order"])
    assert live == []


def test_lock_order_interprocedural_cycle_and_unrelated_locks(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading

        class Mgr:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def take_b(self):
                with self._b:
                    return 1

            def left(self):
                with self._a:
                    return self.take_b()    # a -> b through the call

            def right(self):
                with self._b:
                    with self._a:           # b -> a: cycle
                        return 2

        class Other:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fine(self):
                with self._b:
                    with self._a:   # same attrs, DIFFERENT class: no cycle
                        return 3
        """}, select=["lock-order"])
    assert len(live) == 1
    assert "Mgr._a -> Mgr._b -> Mgr._a" in live[0].message
    assert "Other" not in live[0].message


def test_lock_order_reacquisition_is_self_deadlock(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading

        class Mgr:
            def __init__(self):
                self._lock = threading.Lock()

            def helper(self):
                with self._lock:
                    return 1

            def outer(self):
                with self._lock:
                    return self.helper()    # re-acquire via call: deadlock
        """}, select=["lock-order"])
    assert len(live) == 1
    assert "not reentrant" in live[0].message
    assert "Mgr.helper" in live[0].message


# -- rule: blocking-under-lock -----------------------------------------------


def test_blocking_under_lock_direct_and_one_call_deep(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading
        import time
        import numpy as np

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()

            def bad_direct(self):
                with self._lock:
                    time.sleep(1)           # fires: blocking in the section

            def _write(self, path, x):
                np.savez(path, x=x)         # blocking leaf (clean alone)

            def bad_chain(self):
                with self._lock:
                    self._write("p", 1)     # fires: one call deep

            def flush(self):  # lock-held: _lock
                time.sleep(0)               # fires: caller holds by contract

            def good(self):
                with self._lock:
                    snap = 1
                self._write("p", snap)      # after release: clean
                time.sleep(0)               # no lock: clean
        """}, select=["blocking-under-lock"])
    assert len(live) == 3, [(f.line, f.message) for f in live]
    msgs = sorted(f.message for f in live)
    assert any("blocking call time.sleep()" in m and "Srv._lock" in m
               for m in msgs)
    assert any("call chain" in m and "np.savez()" in m and "Srv._write" in m
               for m in msgs)
    assert sum("time.sleep" in m for m in msgs) == 2  # direct + annotated


def test_blocking_under_lock_condition_wait_is_exempt(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()
                self._lock = threading.Lock()

            def take(self):
                with self._cv:
                    self._cv.wait(0.2)      # Condition releases it: clean

            def bad(self):
                with self._lock:
                    with self._cv:
                        self._cv.wait(0.2)  # _lock stays held: fires
        """}, select=["blocking-under-lock"])
    assert len(live) == 1
    assert "Q._lock" in live[0].message and "wait" in live[0].message


def test_blocking_under_lock_wait_leaf_never_masks_hard_blocking(tmp_path):
    """A helper whose body has an (exemptable) Condition wait AND a hard
    blocking call must witness the HARD one to its callers — otherwise a
    caller holding only the waited-on lock would be silently skipped while
    the disk write runs under it."""
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading
        import numpy as np

        class Q:
            def __init__(self):
                self._cv = threading.Condition()

            def _flush(self):
                self._cv.wait(0.2)
                np.savez("p", x=1)      # the witness callers must see

            def pump(self):
                with self._cv:
                    self._flush()       # fires: savez runs under _cv
        """}, select=["blocking-under-lock"])
    assert len(live) == 1, [(f.line, f.message) for f in live]
    assert "np.savez()" in live[0].message and "Q._cv" in live[0].message


def test_cli_explicit_paths_leave_sidecar_alone(tmp_path):
    """cli.run on explicit paths must not touch the repo-default sidecar
    (the prune-to-scan-set semantics would wipe the whole-tree warm cache)
    nor create one anywhere else, unless cache_dir is explicit."""
    cli = _load_cli()
    repo_sidecar = REPO / ".fedlint_cache" / "facts.json"
    before = repo_sidecar.read_bytes() if repo_sidecar.exists() else None
    (tmp_path / "m.py").write_text(DIRTY_SRC)
    assert cli.run([str(tmp_path / "m.py")], out=io.StringIO(),
                   select=["metric-keys"]) == 1
    after = repo_sidecar.read_bytes() if repo_sidecar.exists() else None
    assert before == after
    assert not (tmp_path / ".fedlint_cache").exists()
    # an explicit cache_dir re-enables caching for explicit paths
    assert cli.run([str(tmp_path / "m.py")], out=io.StringIO(),
                   select=["metric-keys"],
                   cache_dir=str(tmp_path / "cc")) == 1
    assert (tmp_path / "cc" / "facts.json").exists()


def test_blocking_under_lock_wait_helper_chain_is_exempt(tmp_path):
    """The Condition exemption must survive refactoring the wait into a
    helper: a chain whose ONLY held lock is the one the leaf waits on is
    clean; any other lock held across the same chain still fires."""
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()
                self._lock = threading.Lock()

            def _wait_for_it(self):  # lock-held: _cv
                self._cv.wait(0.2)

            def take(self):
                with self._cv:
                    self._wait_for_it()     # waits on the held cv: clean

            def bad(self):
                with self._lock:
                    with self._cv:
                        self._wait_for_it() # _lock held across it: fires
        """}, select=["blocking-under-lock"])
    assert len(live) == 1, [(f.line, f.message) for f in live]
    assert "Q._lock" in live[0].message
    assert "Q._cv" not in live[0].message.split("reaches")[0]


# -- rule: thread-entry ------------------------------------------------------


THREAD_ENTRY_SRC = """
    import threading

    class Mgr:
        def __init__(self):
            self._tally = {}  # guarded-by: _lock
            self._lock = threading.Lock()
            self._timer = None

        def arm(self):
            self._timer = threading.Timer(1.0, self._on_timeout)
            self._timer.start()

        def _on_timeout(self):  # lock-held: _lock
            self._tally["x"] = 1    # timer thread holds NOTHING: the lie

        def spawn(self):
            threading.Thread(target=self._entry).start()

        def _entry(self):
            with self._lock:
                self._locked_helper()

        def _locked_helper(self):  # lock-held: _lock
            return len(self._tally)     # path-held via _entry: clean
    """


def test_thread_entry_timer_callback_assuming_lock_fires(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": THREAD_ENTRY_SRC},
                      select=["thread-entry"])
    assert len(live) == 1, [(f.line, f.message) for f in live]
    f = live[0]
    assert "`Mgr._on_timeout` assumes caller-held Mgr._lock" in f.message
    assert "Timer entry" in f.message
    # the guarded-by rule itself stays clean (the annotation satisfies it)
    live_gb, _, _ = lint(tmp_path, {"m.py": THREAD_ENTRY_SRC},
                         select=["guarded-by"])
    assert live_gb == []


def test_thread_entry_pool_dispatched_closure(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        import threading

        class Mgr:
            def __init__(self):
                self._tally = {}  # guarded-by: _lock
                self._lock = threading.Lock()

            def dispatch(self, pool):
                def work():  # lock-held: _lock
                    return 1
                pool.run_all([(1, work)])
        """}, select=["thread-entry"])
    assert len(live) == 1
    assert "work" in live[0].message and "run_all entry" in live[0].message


def test_thread_entry_lock_taken_on_path_is_clean(tmp_path):
    src = THREAD_ENTRY_SRC.replace(
        'def _on_timeout(self):  # lock-held: _lock\n'
        '            self._tally["x"] = 1    # timer thread holds NOTHING: the lie',
        'def _on_timeout(self):\n'
        '            with self._lock:\n'
        '                self._tally["x"] = 1')
    live, _, _ = lint(tmp_path, {"m.py": src}, select=["thread-entry"])
    assert live == []


# -- waivers -----------------------------------------------------------------


def test_justified_waiver_suppresses_but_stays_enumerable(tmp_path):
    live, all_findings, waivers = lint(tmp_path, {"m.py": """
        def record(log):
            log("Comm/Adhoc")  # fedlint: disable=metric-keys -- fixture literal
        """}, select=["metric-keys"])
    assert live == []
    waived = [f for f in all_findings if f.waived]
    assert len(waived) == 1
    assert waived[0].waiver_reason == "fixture literal"
    assert len(waivers) == 1 and waivers[0].used


def test_unjustified_waiver_is_itself_a_finding(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        def record(log):
            log("Comm/Adhoc")  # fedlint: disable=metric-keys
        """}, select=["metric-keys"])
    # the original finding stays live AND the bare directive is flagged
    assert sorted(f.rule for f in live) == ["metric-keys", "waiver"]
    assert any("no justification" in f.message for f in live)


def test_unused_waiver_is_flagged(tmp_path):
    live, _, _ = lint(tmp_path, {"m.py": """
        def clean():  # fedlint: disable=metric-keys -- nothing here fires
            return 0
        """}, select=["metric-keys"])
    assert [f.rule for f in live] == ["waiver"]
    assert "suppresses nothing" in live[0].message


def test_standalone_waiver_covers_next_line(tmp_path):
    live, all_findings, _ = lint(tmp_path, {"m.py": """
        def record(log):
            # fedlint: disable=metric-keys -- standalone directive form
            log("Comm/Adhoc")
        """}, select=["metric-keys"])
    assert live == []
    assert [f.waiver_reason for f in all_findings] == [
        "standalone directive form"
    ]


# -- report schema / config / CLI -------------------------------------------


def test_json_report_schema(tmp_path):
    _, all_findings, waivers = lint(tmp_path, {"m.py": """
        def record(log):
            log("Comm/Adhoc")
        """}, select=["metric-keys"])
    doc = json.loads(render_json(all_findings, waivers, ["m.py"],
                                 ["metric-keys"]))
    assert doc["schema_version"] == 1
    assert doc["rules"] == ["metric-keys"]
    assert doc["files_scanned"] == ["m.py"]
    assert doc["summary"] == {"findings": 1, "waived": 0, "files": 1}
    (finding,) = doc["findings"]
    assert set(finding) == {"rule", "path", "line", "col", "message",
                            "waived", "waiver_reason"}


def test_unknown_rule_selection_raises():
    cfg = dataclasses.replace(FedlintConfig(), select=("no-such-rule",))
    with pytest.raises(ValueError, match="no-such-rule"):
        make_rules(cfg)


def test_config_fallback_parser_and_repo_section():
    section = _parse_fallback(textwrap.dedent("""
        [tool.other]
        paths = ["nope"]
        [tool.fedlint]
        # comment
        paths = ["a", "b"]
        select = ["guarded-by"]
        flag = true
        """))
    assert section == {"paths": ["a", "b"], "select": ["guarded-by"],
                       "flag": True}
    cfg = load_config(REPO)
    assert cfg.paths == ("fedml_tpu", "tools")
    assert set(cfg.select) == {
        "guarded-by", "overwrite-after-super", "wire-contract",
        "traced-purity", "metric-keys",
        "lock-order", "blocking-under-lock", "thread-entry",
    }


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "fedlint_cli", REPO / "tools" / "fedlint.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_exit_codes(tmp_path):
    cli = _load_cli()
    (tmp_path / "dirty.py").write_text(
        'def f(log):\n    log("Comm/Adhoc")\n'
    )
    out = io.StringIO()
    assert cli.run([str(tmp_path / "dirty.py")], out=out) == 1
    assert "Comm/Adhoc" in out.getvalue()
    (tmp_path / "clean.py").write_text("def f():\n    return 0\n")
    assert cli.run([str(tmp_path / "clean.py")], out=io.StringIO()) == 0
    assert cli.main(["--list-rules"]) == 0


# -- facts cache -------------------------------------------------------------


DIRTY_SRC = 'def f(log):\n    log("Comm/Adhoc")\n'


def _run_with_cache(tmp_path, use_cache=True):
    cfg = dataclasses.replace(FedlintConfig(), select=("metric-keys",))
    findings, _, scanned = run_analysis(
        [str(tmp_path)], make_rules(cfg), root=str(tmp_path),
        use_cache=use_cache,
    )
    return live_findings(findings), scanned


def test_cache_coherence_and_no_cache_bypass(tmp_path):
    """The sidecar serves unchanged files, any (mtime, size) change falls
    back to a fresh parse, and --no-cache really bypasses it — proven by
    poisoning the cached facts and watching each path react."""
    from fedml_tpu.analysis.facts import FACTS_SCHEMA_VERSION, FileFacts

    (tmp_path / "m.py").write_text(DIRTY_SRC)
    live1, _ = _run_with_cache(tmp_path)
    assert len(live1) == 1
    sidecar = tmp_path / ".fedlint_cache" / "facts.json"
    assert sidecar.exists()
    # poison the cached entry (keep the key valid): a cached run must now
    # report NOTHING — this proves facts really come from the cache
    doc = json.loads(sidecar.read_text())
    assert doc["version"] == FACTS_SCHEMA_VERSION
    doc["entries"]["m.py"]["facts"] = FileFacts("m.py").to_dict()
    sidecar.write_text(json.dumps(doc))
    live_poisoned, _ = _run_with_cache(tmp_path)
    assert live_poisoned == []
    # use_cache=False bypasses the poison (CLI --no-cache)
    live_nocache, _ = _run_with_cache(tmp_path, use_cache=False)
    assert len(live_nocache) == 1
    # stale-cache regression: rewriting the file (mtime/size move)
    # invalidates the poisoned entry and findings come back
    (tmp_path / "m.py").write_text(DIRTY_SRC + "\n# touched\n")
    live_fresh, _ = _run_with_cache(tmp_path)
    assert len(live_fresh) == 1
    # a corrupt sidecar degrades to a cold run, never an error
    sidecar.write_text("{not json")
    live_corrupt, _ = _run_with_cache(tmp_path)
    assert len(live_corrupt) == 1


def test_cache_prunes_deleted_files(tmp_path):
    (tmp_path / "keep.py").write_text("def f():\n    return 0\n")
    (tmp_path / "gone.py").write_text("def g():\n    return 1\n")
    _run_with_cache(tmp_path)
    sidecar = tmp_path / ".fedlint_cache" / "facts.json"
    assert set(json.loads(sidecar.read_text())["entries"]) == {
        "keep.py", "gone.py"}
    (tmp_path / "gone.py").unlink()
    _run_with_cache(tmp_path)
    # deleted files never accumulate dead entries in the sidecar
    assert set(json.loads(sidecar.read_text())["entries"]) == {"keep.py"}


def test_cache_warm_run_halves_wall_time(tmp_path):
    """The tier-1 budget guard: over the real fedml_tpu/ + tools/ tree, a
    warm-cache run must cost <= 50% of the cold run (the acceptance bar
    that keeps the gate's cost flat as rules grow)."""
    import time

    cfg = load_config(REPO)
    paths = [str(REPO / p) for p in cfg.paths]
    cache_dir = tmp_path / "cache"

    def one_run():
        t0 = time.perf_counter()
        findings, _, scanned = run_analysis(
            paths, make_rules(cfg), exclude=cfg.exclude, root=str(REPO),
            cache_dir=cache_dir,
        )
        return time.perf_counter() - t0, findings, scanned

    cold_t, cold_findings, cold_scanned = one_run()
    warm_t, warm_findings, warm_scanned = min(
        (one_run() for _ in range(2)), key=lambda r: r[0])
    assert warm_scanned == cold_scanned and len(warm_scanned) > 100
    assert ([f.to_dict() for f in warm_findings]
            == [f.to_dict() for f in cold_findings])
    assert warm_t <= 0.5 * cold_t, (warm_t, cold_t)


# -- SARIF / baseline --------------------------------------------------------


def test_sarif_output_is_schema_shaped(tmp_path):
    cli = _load_cli()
    (tmp_path / "dirty.py").write_text(DIRTY_SRC)
    (tmp_path / "waived.py").write_text(
        'def g(log):\n'
        '    log("Comm/Adhoc2")  # fedlint: disable=metric-keys -- fixture\n'
    )
    out = io.StringIO()
    rc = cli.run([str(tmp_path)], fmt="sarif", out=out,
                 select=["metric-keys"])
    assert rc == 1
    doc = json.loads(out.getvalue())
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "fedlint"
    assert {r["id"] for r in driver["rules"]} >= {"metric-keys"}
    assert all(r["shortDescription"]["text"] for r in driver["rules"])
    results = run["results"]
    assert len(results) == 2
    for res in results:
        assert res["ruleId"] == "metric-keys"
        assert res["level"] == "error" and res["message"]["text"]
        (loc,) = res["locations"]
        region = loc["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1
        assert loc["physicalLocation"]["artifactLocation"]["uri"]
    suppressed = [r for r in results if r.get("suppressions")]
    assert len(suppressed) == 1
    (sup,) = suppressed[0]["suppressions"]
    assert sup["kind"] == "inSource" and sup["justification"] == "fixture"


def test_baseline_diff_mode_exit_codes(tmp_path):
    """--baseline: exit 0 when every live finding is already in the saved
    report, 1 (reporting ONLY the new ones) otherwise; a malformed
    baseline fails loudly."""
    cli = _load_cli()
    target = tmp_path / "m.py"
    target.write_text(DIRTY_SRC)
    base = io.StringIO()
    assert cli.run([str(target)], fmt="json", out=base,
                   select=["metric-keys"]) == 1
    baseline = tmp_path / "baseline.json"
    baseline.write_text(base.getvalue())
    # unchanged tree: everything carried -> gate passes; the carried-count
    # line is DIAGNOSTIC (stderr) — stdout stays one parseable document
    out, errs = io.StringIO(), io.StringIO()
    assert cli.run([str(target)], fmt="json", out=out, err=errs,
                   select=["metric-keys"], baseline=str(baseline)) == 0
    assert "1 carried finding(s) suppressed, 0 new" in errs.getvalue()
    json.loads(out.getvalue())
    # a NEW finding fails the gate and is the only one rendered
    target.write_text(DIRTY_SRC + 'def g(log):\n    log("Comm/Fresh")\n')
    out, errs = io.StringIO(), io.StringIO()
    assert cli.run([str(target)], fmt="json", out=out, err=errs,
                   select=["metric-keys"], baseline=str(baseline)) == 1
    assert "1 carried finding(s) suppressed, 1 new" in errs.getvalue()
    doc = json.loads(out.getvalue())
    assert doc["summary"]["findings"] == 1
    assert "Comm/Fresh" in doc["findings"][0]["message"]
    # malformed baseline: loud failure, not silently-all-new
    bad = tmp_path / "bad.json"
    bad.write_text("[]")
    with pytest.raises(ValueError, match="not a fedlint"):
        cli.run([str(target)], select=["metric-keys"], baseline=str(bad),
                out=io.StringIO())


# -- the tier-1 gate ---------------------------------------------------------


def test_repo_is_clean():
    """The gate: zero live findings and zero unjustified waivers over
    fedml_tpu/ and tools/ with ALL rules — the interprocedural concurrency
    set included — and every waiver carrying its justification."""
    cli = _load_cli()
    out = io.StringIO()
    rc = cli.run(fmt="json", out=out)
    doc = json.loads(out.getvalue())
    live = [f for f in doc["findings"] if not f["waived"]]
    assert rc == 0 and live == [], live
    assert doc["summary"]["files"] > 100  # the whole package, not a subset
    assert set(doc["rules"]) >= {
        "guarded-by", "overwrite-after-super", "wire-contract",
        "traced-purity", "metric-keys",
        "lock-order", "blocking-under-lock", "thread-entry",
    }
    for f in doc["findings"]:  # waived: justification is mandatory
        assert f["waiver_reason"], f
    for w in doc["waivers"]:
        assert w["used"] and w["reason"], w
