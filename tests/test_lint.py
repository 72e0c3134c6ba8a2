"""mxlint: per-rule positive/negative fixtures, the suppression machinery,
the baseline round-trip, and the tier-1 full-tree gate.

The full-tree test at the bottom is the actual invariant: the rules that
six PRs paid for (no host sync in dispatch bodies, perf_counter for
durations, no imports in signal handlers, registered env vars, ...) fail CI
the moment a change breaks them.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MXLINT = os.path.join(_REPO, "tools", "mxlint.py")

_spec = importlib.util.spec_from_file_location("mxlint", _MXLINT)
mxlint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(mxlint)


def lint_src(tmp_path, src, relpath="mxnet_tpu/fixture.py", rules=None,
             hot_entries=None, env_registry=frozenset(), pass_entries=None):
    """Write one fixture file under a fake repo root and lint it."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    findings, stats = mxlint.run_lint(
        [str(path)], root=str(tmp_path), rules=rules,
        hot_entries=hot_entries if hot_entries is not None else {},
        env_registry=env_registry,
        pass_entries=pass_entries if pass_entries is not None else {})
    return findings, stats


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# hot-sync
# ---------------------------------------------------------------------------
HOT = {"mxnet_tpu/fixture.py": ("Step._step_impl",)}

def test_hot_sync_direct_readback_flagged(tmp_path):
    findings, _ = lint_src(tmp_path, """
        class Step:
            def _step_impl(self, loss):
                return float(loss)
        """, hot_entries=HOT)
    assert rules_of(findings) == ["hot-sync"]
    assert findings[0].context == "Step._step_impl"


def test_hot_sync_reaches_through_call_graph(tmp_path):
    # entry -> self method -> module function -> np.asarray
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        def _materialize(x):
            return np.asarray(x)

        class Step:
            def _step_impl(self, x):
                return self._place(x)

            def _place(self, x):
                return _materialize(x)
        """, hot_entries=HOT)
    assert rules_of(findings) == ["hot-sync"]
    assert findings[0].context == "_materialize"


def test_hot_sync_method_syncs_flagged(tmp_path):
    findings, _ = lint_src(tmp_path, """
        class Step:
            def _step_impl(self, loss):
                loss.block_until_ready()
                return loss.item()
        """, hot_entries=HOT)
    assert sorted(rules_of(findings)) == ["hot-sync", "hot-sync"]


def test_hot_sync_ignores_cold_functions_and_literals(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        class Step:
            def _step_impl(self, x):
                scale = float(1e-3)              # constant: no readback
                arr = np.asarray([1.0, 2.0])     # host literal
                return scale, arr

            def sync_to_block(self, x):
                return float(x)                  # NOT a per-step body
        """, hot_entries=HOT)
    assert findings == []


def test_hot_sync_flags_memory_apis_in_dispatch(tmp_path):
    """PR 8: memory polling (memory_stats / live_arrays /
    memory_analysis) must never run inside a per-step dispatch body —
    sample via memwatch at step boundaries instead."""
    findings, _ = lint_src(tmp_path, """
        import jax

        class Step:
            def _step_impl(self, dev, compiled):
                stats = dev.memory_stats()
                live = jax.live_arrays()
                ma = compiled.memory_analysis()
                return stats, live, ma
        """, hot_entries=HOT)
    assert rules_of(findings) == ["hot-sync"] * 3
    assert all("memwatch" in f.message or "memory" in f.message
               for f in findings)


def test_hot_sync_flags_live_arrays_from_import(tmp_path):
    findings, _ = lint_src(tmp_path, """
        from jax import live_arrays

        class Step:
            def _step_impl(self):
                return live_arrays()
        """, hot_entries=HOT)
    assert rules_of(findings) == ["hot-sync"]


def test_hot_sync_memory_apis_allowed_off_hot_path(tmp_path):
    """The same calls at a step boundary (not reachable from a dispatch
    body) are exactly where the memwatch sampler runs — clean."""
    findings, _ = lint_src(tmp_path, """
        import jax

        class Step:
            def _step_impl(self, x):
                return x

            def on_step_boundary(self, dev):
                return dev.memory_stats(), jax.live_arrays()
        """, hot_entries=HOT)
    assert findings == []


# ---------------------------------------------------------------------------
# wall-clock-duration
# ---------------------------------------------------------------------------
def test_wall_clock_duration_local_and_attr_flagged(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import time

        def f():
            t0 = time.time()
            work()
            return time.time() - t0

        class H:
            def begin(self):
                self.t0 = time.time()

            def end(self):
                return time.time() - self.t0
        """)
    assert rules_of(findings) == ["wall-clock-duration",
                                  "wall-clock-duration"]


def test_wall_clock_cross_process_age_not_flagged(tmp_path):
    # age vs a wall stamp read from another process's file is the
    # legitimate use of time.time() (heartbeats) — must stay clean
    findings, _ = lint_src(tmp_path, """
        import time

        def age(rec):
            return time.time() - float(rec.get("time", 0.0))

        def ok():
            t0 = time.perf_counter()
            work()
            return time.perf_counter() - t0
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------
def test_retrace_hazard_jit_in_hot_path(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import jax

        class Step:
            def _step_impl(self, f, x):
                return jax.jit(f)(x)
        """, hot_entries=HOT)
    assert rules_of(findings) == ["retrace-hazard"]


def test_retrace_hazard_unhashable_static_arg(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import jax

        g = jax.jit(run, static_argnums=(1,))

        def call(x):
            bad = g(x, [4, 8])       # list literal in a static position
            ok = g(x, (4, 8))        # hashable tuple: fine
            return bad, ok
        """)
    assert rules_of(findings) == ["retrace-hazard"]
    assert "unhashable" in findings[0].message


def test_jit_outside_hot_path_not_flagged(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import jax

        class Step:
            def _step_impl(self, x):
                return x

        def build(f):
            return jax.jit(f)
        """, hot_entries=HOT)
    assert findings == []


def test_stale_hot_entry_is_a_finding(tmp_path):
    # a renamed dispatch body must not silently no-op the flagship rule
    findings, _ = lint_src(tmp_path, """
        class Step:
            def _step_impl_renamed(self, x):
                return x
        """, hot_entries=HOT)
    assert rules_of(findings) == ["stale-hot-entry"]
    assert "Step._step_impl" in findings[0].message


def test_step_entries_registered_and_rename_fails_loudly(tmp_path):
    """The training step's dispatch body and the prefetcher's staging
    half are in the REAL HOT_PATH_ENTRIES, nothing else of that file is,
    and renaming one in a fixture carrying those entries flags
    stale-hot-entry rather than silently un-linting the path."""
    real = mxlint.HOT_PATH_ENTRIES["mxnet_tpu/parallel/data_parallel.py"]
    assert set(real) == {"DataParallelStep._step_impl",
                         "DataParallelStep.stage",
                         "DataParallelStep._plan_dispatch"}

    entries = {"mxnet_tpu/fixture.py": ("DataParallelStep._step_impl",
                                        "DataParallelStep.stage")}
    findings, _ = lint_src(tmp_path, """
        class DataParallelStep:
            def _step_impl(self, data, label):
                return data

            def stage_renamed(self, data, label):
                return data
        """, hot_entries=entries)
    assert rules_of(findings) == ["stale-hot-entry"]
    assert "DataParallelStep.stage" in findings[0].message
    # a host readback reachable from the staging half is flagged like
    # any hot path
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        class DataParallelStep:
            def _step_impl(self, data, label):
                return data

            def stage(self, data, label):
                return np.asarray(data)
        """, hot_entries=entries)
    assert rules_of(findings) == ["hot-sync"]


# ---------------------------------------------------------------------------
# precision subsystem entries (ISSUE 15): the loss-scale shim and the
# int8 decode body are hot paths; the OLD per-gradient readback pattern
# must be flagged if ever reintroduced
# ---------------------------------------------------------------------------
def test_precision_entries_registered():
    assert mxlint.HOT_PATH_ENTRIES["mxnet_tpu/precision/loss_scale.py"] \
        == ("overflow_flag",)
    # the decode body lives on the shared rewrite-adapter base since the
    # int4 path joined int8 (both delegate through it)
    assert mxlint.HOT_PATH_ENTRIES["mxnet_tpu/precision/quantize.py"] \
        == ("_RewriteAdapterBase.decode",)
    amp_entries = mxlint.HOT_PATH_ENTRIES["mxnet_tpu/contrib/amp/amp.py"]
    assert "DynamicLossScaler.has_overflow" in amp_entries


def test_old_scaler_readback_pattern_would_be_flagged(tmp_path):
    """The pre-PR-15 DynamicLossScaler.has_overflow body — one blocking
    asnumpy() PER GRADIENT inside the per-step path — fires hot-sync
    under the entry now registered for the shim.  Reintroducing the old
    pattern cannot land silently."""
    entries = {"mxnet_tpu/fixture.py": ("DynamicLossScaler.has_overflow",)}
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        class DynamicLossScaler:
            def has_overflow(self, params):
                for param in params:
                    for g in param.list_grad():
                        arr = g.asnumpy()
                        if not np.isfinite(arr).all():
                            return True
                return False
        """, hot_entries=entries)
    assert rules_of(findings) == ["hot-sync"]
    assert ".asnumpy()" in findings[0].message


def test_new_scaler_shim_shape_is_clean(tmp_path):
    """The fused-delegate shim shape — collect raw grad buffers, ONE
    fused device reduce, one justified boundary readback — lints clean
    under the same entry."""
    entries = {"mxnet_tpu/fixture.py": ("DynamicLossScaler.has_overflow",)}
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        def overflow_flag(arrays):
            return arrays

        class DynamicLossScaler:
            def has_overflow(self, params):
                grads = [g._data for p in params for g in p.list_grad()]
                if not grads:
                    return False
                flag = overflow_flag(grads)
                # mxlint: disable=hot-sync — ONE readback at the eager
                # python-bool API boundary
                return bool(np.asarray(flag))
        """, hot_entries=entries)
    assert rules_of(findings) == []


def test_quantized_decode_body_guarded(tmp_path):
    """A host readback sneaking into the int8 adapter's decode body (the
    trace body of the ONE quantized executable) is flagged."""
    entries = {"mxnet_tpu/fixture.py": ("QuantizedAdapter.decode",)}
    findings, _ = lint_src(tmp_path, """
        class QuantizedAdapter:
            def decode(self, F, tok):
                return float(tok.sum())
        """, hot_entries=entries)
    assert rules_of(findings) == ["hot-sync"]
    findings, _ = lint_src(tmp_path, """
        class QuantizedAdapter:
            def decode(self, F, tok):
                return self._inner.decode(F, tok)
        """, hot_entries=entries)
    assert rules_of(findings) == []


def test_precision_entry_rename_fails_loudly(tmp_path):
    entries = {"mxnet_tpu/fixture.py": ("overflow_flag",)}
    findings, _ = lint_src(tmp_path, """
        def overflow_flag_renamed(arrays):
            return arrays
        """, hot_entries=entries)
    assert rules_of(findings) == ["stale-hot-entry"]
    assert "overflow_flag" in findings[0].message


# ---------------------------------------------------------------------------
# signal-unsafe
# ---------------------------------------------------------------------------
def test_signal_unsafe_import_open_acquire_flagged(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import signal

        def install(lock):
            def _handler(signum, frame):
                import os
                open("/tmp/x", "w")
                lock.acquire()

            signal.signal(signal.SIGTERM, _handler)
        """)
    assert sorted(rules_of(findings)) == ["signal-unsafe"] * 3


def test_signal_safe_handler_clean(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import signal
        import sys

        def install():
            def _handler(signum, frame):
                mod = sys.modules.get("mxnet_tpu.parallel.async_loss")
                if mod is not None:
                    mod.drain_all()
                print("preempted", flush=True)

            signal.signal(signal.SIGTERM, _handler)
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# thread-shared-write (the race detector)
# ---------------------------------------------------------------------------
def test_race_worker_and_consumer_write_unlocked(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import threading

        class Iter:
            def start(self):
                self._thread = threading.Thread(target=self._worker)
                self._thread.start()

            def _worker(self):
                self.cursor = self.cursor + 1

            def reset(self):
                self.cursor = 0
        """)
    assert rules_of(findings) == ["thread-shared-write"]
    assert "cursor" in findings[0].message


def test_race_clean_when_both_sides_hold_the_lock(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import threading

        class Iter:
            def start(self):
                self._lock = threading.Lock()
                self._thread = threading.Thread(target=self._worker)
                self._thread.start()

            def _worker(self):
                with self._lock:
                    self.cursor = self.cursor + 1

            def reset(self):
                with self._lock:
                    self.cursor = 0
        """)
    assert findings == []


def test_race_init_writes_are_pre_thread_and_safe(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import threading

        class Iter:
            def __init__(self):
                self.cursor = 0      # before the thread exists: safe
                threading.Thread(target=self._worker).start()

            def _worker(self):
                self.cursor = self.cursor + 1
        """)
    assert findings == []


def test_race_nested_worker_fn_not_its_own_consumer(tmp_path):
    # a nested Thread target's writes are worker-side ONLY — they must not
    # also register as a "consumer method" and race with themselves
    findings, _ = lint_src(tmp_path, """
        import threading

        class Iter:
            def start(self):
                def worker():
                    self.count = self.count + 1

                threading.Thread(target=worker).start()
        """)
    assert findings == []
    # ...but a real consumer-side write still races with the nested worker
    findings, _ = lint_src(tmp_path, """
        import threading

        class Iter:
            def start(self):
                def worker():
                    self.count = self.count + 1

                threading.Thread(target=worker).start()

            def reset(self):
                self.count = 0
        """)
    assert rules_of(findings) == ["thread-shared-write"]


def test_race_threaded_iter_produce_is_worker_side(tmp_path):
    findings, _ = lint_src(tmp_path, """
        class _ThreadedIter:
            pass

        class Prefetch(_ThreadedIter):
            def _produce(self):
                self.count = self.count + 1

            def reset(self):
                self.count = 0
        """)
    assert rules_of(findings) == ["thread-shared-write"]


# ---------------------------------------------------------------------------
# silent-except
# ---------------------------------------------------------------------------
def test_silent_except_flagged_and_justification_accepted(tmp_path):
    findings, _ = lint_src(tmp_path, """
        def bad():
            try:
                work()
            except Exception:
                pass

        def justified():
            try:
                work()
            except Exception:
                # best-effort teardown while already dying
                pass

        def narrow():
            import queue
            try:
                work()
            except queue.Empty:
                pass
        """)
    assert rules_of(findings) == ["silent-except"]
    assert findings[0].line == 5  # the `except Exception:` line


def test_silent_except_bare_and_tuple_broad(tmp_path):
    findings, _ = lint_src(tmp_path, """
        def f():
            try:
                work()
            except (ValueError, Exception):
                pass
        """)
    assert rules_of(findings) == ["silent-except"]


# ---------------------------------------------------------------------------
# env-unregistered
# ---------------------------------------------------------------------------
def test_env_unregistered_ast_level(tmp_path):
    findings, _ = lint_src(tmp_path, '''
        """Docstring mentioning "MX_NOT_A_READ" is prose, not a use-site."""
        import os

        KNOWN = os.environ.get("MX_KNOWN_KNOB", "1")
        DRIFT = os.environ.get("MX_DRIFTED_KNOB")
        ''', env_registry={"MX_KNOWN_KNOB"})
    assert rules_of(findings) == ["env-unregistered"]
    assert "MX_DRIFTED_KNOB" in findings[0].message


def test_env_rule_scope_excludes_examples(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import os

        os.environ.setdefault("MX_DRIFTED_KNOB", "1")
        """, relpath="examples/fixture.py", env_registry=set())
    assert findings == []


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------
def test_suppression_trailing_and_own_line(tmp_path):
    findings, stats = lint_src(tmp_path, """
        import time

        def f():
            t0 = time.time()
            dt = time.time() - t0  # mxlint: disable=wall-clock-duration ok

        def g():
            t0 = time.time()
            # mxlint: disable=wall-clock-duration — cross-epoch wall fact
            # (continuation of the justification)
            dt = time.time() - t0
        """)
    assert findings == []
    assert stats["suppressed"] == 2


def test_suppression_comma_in_justification_not_a_rule(tmp_path):
    # "disable=<rule>, free text" must not read the free text as rules
    findings, _ = lint_src(tmp_path, """
        import time

        def f():
            t0 = time.time()
            dt = time.time() - t0  # mxlint: disable=wall-clock-duration, staged input path
        """)
    assert findings == []
    # ...but a lone unknown word after the comma is still a typo finding
    findings, _ = lint_src(tmp_path, """
        import time

        def f():
            t0 = time.time()
            dt = time.time() - t0  # mxlint: disable=wall-clock-duration,wall-clck
        """)
    assert rules_of(findings) == ["bad-suppression"]


def test_nested_function_finding_not_duplicated(tmp_path):
    # a nested fn's body is walked via the enclosing scope AND as its own
    # entry; one defect must yield exactly one finding (and one baseline
    # fingerprint)
    findings, _ = lint_src(tmp_path, """
        import time

        def outer():
            def inner():
                t0 = time.time()
                return time.time() - t0

            return inner
        """)
    assert rules_of(findings) == ["wall-clock-duration"]


def test_suppression_wrong_rule_does_not_silence(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import time

        def f():
            t0 = time.time()
            dt = time.time() - t0  # mxlint: disable=hot-sync
        """)
    assert rules_of(findings) == ["wall-clock-duration"]


def test_unknown_rule_in_suppression_is_a_finding(tmp_path):
    findings, _ = lint_src(tmp_path, """
        x = 1  # mxlint: disable=definitely-not-a-rule
        """)
    assert rules_of(findings) == ["bad-suppression"]
    assert "definitely-not-a-rule" in findings[0].message


def test_rules_filter_and_unknown_rule_rejected(tmp_path):
    findings, _ = lint_src(tmp_path, """
        import time

        def f():
            try:
                t0 = time.time()
                return time.time() - t0
            except Exception:
                pass
        """, rules=["silent-except"])
    assert rules_of(findings) == ["silent-except"]
    with pytest.raises(ValueError, match="unknown rule"):
        mxlint.run_lint([str(tmp_path)], root=str(tmp_path),
                        rules=["no-such-rule"])


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------
def _one_finding_repo(tmp_path):
    (tmp_path / "mxnet_tpu").mkdir(parents=True, exist_ok=True)
    f = tmp_path / "mxnet_tpu" / "mod.py"
    f.write_text(textwrap.dedent("""
        import time

        def f():
            t0 = time.time()
            return time.time() - t0
        """))
    return f


def test_baseline_roundtrip_add_then_remove(tmp_path):
    src = _one_finding_repo(tmp_path)
    findings, _ = mxlint.run_lint([str(src)], root=str(tmp_path),
                                  hot_entries={}, env_registry=set())
    assert len(findings) == 1
    bl = tmp_path / "baseline.json"

    # write: the new entry is marked for review
    entries = mxlint.write_baseline(str(bl), findings, str(tmp_path), [])
    assert len(entries) == 1
    assert entries[0]["justification"].startswith("UNREVIEWED")

    # a reviewed justification survives a rewrite (carried by fingerprint)
    entries[0]["justification"] = "epoch wall is a cross-run fact"
    bl.write_text(json.dumps({"version": 1, "entries": entries}))
    entries2 = mxlint.write_baseline(str(bl), findings, str(tmp_path),
                                     mxlint.load_baseline(str(bl)))
    assert entries2[0]["justification"] == "epoch wall is a cross-run fact"

    # apply: finding is baselined away -> clean
    new, baselined, stale = mxlint.apply_baseline(
        findings, mxlint.load_baseline(str(bl)), str(tmp_path))
    assert new == [] and len(baselined) == 1 and stale == []

    # fix the code -> the entry goes stale and is reported for removal
    src.write_text(src.read_text().replace("time.time", "time.perf_counter"))
    findings, _ = mxlint.run_lint([str(src)], root=str(tmp_path),
                                  hot_entries={}, env_registry=set())
    assert findings == []
    new, baselined, stale = mxlint.apply_baseline(
        findings, mxlint.load_baseline(str(bl)), str(tmp_path))
    assert new == [] and baselined == [] and len(stale) == 1


def test_baseline_is_line_number_independent(tmp_path):
    src = _one_finding_repo(tmp_path)
    findings, _ = mxlint.run_lint([str(src)], root=str(tmp_path),
                                  hot_entries={}, env_registry=set())
    bl = tmp_path / "baseline.json"
    mxlint.write_baseline(str(bl), findings, str(tmp_path), [])
    # shift the finding down: unrelated edits above must not un-baseline it
    src.write_text("# leading comment\n\n" + src.read_text())
    findings, _ = mxlint.run_lint([str(src)], root=str(tmp_path),
                                  hot_entries={}, env_registry=set())
    new, baselined, stale = mxlint.apply_baseline(
        findings, mxlint.load_baseline(str(bl)), str(tmp_path))
    assert new == [] and len(baselined) == 1 and stale == []


def test_malformed_baseline_rejected(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text('{"entries": [{"nope": 1}]}')
    with pytest.raises(ValueError, match="malformed"):
        mxlint.load_baseline(str(bl))


def test_write_baseline_with_rules_subset_preserves_other_entries(tmp_path):
    # --rules silent-except --write-baseline must NOT delete (or
    # un-justify) entries owned by rules that didn't run
    src = _one_finding_repo(tmp_path)   # wall-clock-duration finding
    bl = tmp_path / "baseline.json"
    findings, _ = mxlint.run_lint([str(src)], root=str(tmp_path),
                                  hot_entries={}, env_registry=set())
    entries = mxlint.write_baseline(str(bl), findings, str(tmp_path), [])
    entries[0]["justification"] = "reviewed: epoch wall fact"
    bl.write_text(json.dumps({"version": 1, "entries": entries}))

    p = _cli(["mxnet_tpu", "--root", str(tmp_path), "--baseline", str(bl),
              "--rules", "silent-except", "--write-baseline"],
             cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr
    kept = mxlint.load_baseline(str(bl))
    assert len(kept) == 1, kept
    assert kept[0]["justification"] == "reviewed: epoch wall fact"


def test_write_baseline_rejects_malformed_existing(tmp_path):
    # the write path must not silently regenerate over a corrupt file,
    # discarding every reviewed justification
    _one_finding_repo(tmp_path)
    bl = tmp_path / "baseline.json"
    bl.write_text("{not json")
    p = _cli(["mxnet_tpu", "--root", str(tmp_path), "--baseline", str(bl),
              "--write-baseline"], cwd=str(tmp_path))
    assert p.returncode == 2
    assert "unreadable" in p.stderr
    assert bl.read_text() == "{not json"


# ---------------------------------------------------------------------------
# CLI contract (exit codes + --json schema, documented in
# docs/STATIC_ANALYSIS.md for supervisor/trace_report consumption)
# ---------------------------------------------------------------------------
def _cli(args, cwd):
    return subprocess.run([sys.executable, _MXLINT] + args, cwd=cwd,
                          capture_output=True, text=True, timeout=60)


def test_cli_exit_codes_and_json_schema(tmp_path):
    _one_finding_repo(tmp_path)
    p = _cli(["mxnet_tpu", "--root", str(tmp_path), "--no-baseline",
              "--json"], cwd=str(tmp_path))
    assert p.returncode == 3, p.stderr
    rep = json.loads(p.stdout)
    for key in ("version", "files_scanned", "elapsed_s", "counts",
                "findings", "suppressed", "baselined", "stale_baseline"):
        assert key in rep, key
    assert rep["counts"] == {"wall-clock-duration": 1}
    f = rep["findings"][0]
    for key in ("rule", "path", "line", "col", "context", "message"):
        assert key in f, key
    assert f["path"] == "mxnet_tpu/mod.py"

    # clean tree -> 0
    (tmp_path / "mxnet_tpu" / "mod.py").write_text("x = 1\n")
    p = _cli(["mxnet_tpu", "--root", str(tmp_path), "--no-baseline"],
             cwd=str(tmp_path))
    assert p.returncode == 0, p.stderr

    # usage error -> 2
    p = _cli(["--rules", "bogus", "--root", str(tmp_path)],
             cwd=str(tmp_path))
    assert p.returncode == 2
    assert "unknown rule" in p.stderr


def test_serving_dispatch_entry_registered_and_rename_fails_loudly(tmp_path):
    """The serving engine's decode-dispatch body is in the REAL
    HOT_PATH_ENTRIES (a host sync there would serialize the whole
    serving pipeline), and renaming it in a fixture carrying the entry
    flags stale-hot-entry rather than silently un-linting the path."""
    real = mxlint.HOT_PATH_ENTRIES["mxnet_tpu/serving/engine.py"]
    assert "ServingEngine._dispatch_step" in real

    entries = {"mxnet_tpu/fixture.py": ("ServingEngine._dispatch_step",)}
    findings, _ = lint_src(tmp_path, """
        class ServingEngine:
            def _dispatch_step_renamed(self):
                return None
        """, hot_entries=entries)
    assert rules_of(findings) == ["stale-hot-entry"]
    assert "ServingEngine._dispatch_step" in findings[0].message

    # positive: a per-token host readback reachable from the dispatch
    # body (the exact bug the serving refactor removed from translate)
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        class ServingEngine:
            def _dispatch_step(self):
                outs = self._run()
                return self._emit(outs)

            def _emit(self, outs):
                return np.asarray(outs[0])   # per-token sync: flagged

            def _run(self):
                return (object(),)
        """, hot_entries=entries)
    assert rules_of(findings) == ["hot-sync"]
    assert findings[0].context == "ServingEngine._emit"

    # negative: the real body's shape — chain device state, admit the
    # lazy handle, stamp the compile wall — carries no syncs
    findings, _ = lint_src(tmp_path, """
        import time

        class ServingEngine:
            def _dispatch_step(self):
                self._ring.make_room(self._window)
                arrays = [a._data for a in self._state.values()]
                t0 = time.perf_counter()
                outs = self._run(self._params(), *arrays)
                handle = self._wrap(outs[0])
                self._ring.admit(handle)
                return handle

            def _params(self):
                return tuple(p.data() for _, p in self._param_items)

            def _wrap(self, toks):
                return toks
        """, hot_entries=entries)
    assert findings == []


def test_serving_front_door_entries_registered(tmp_path):
    """PR 17's jitted bodies (sampled decode, speculative verify, prefix
    ingest) and the spec dispatch are in the REAL HOT_PATH_ENTRIES, and
    the replica/router HTTP handlers are in the REAL JAX_FREE_ENTRIES."""
    real = mxlint.HOT_PATH_ENTRIES["mxnet_tpu/serving/engine.py"]
    for entry in ("ServingEngine._dispatch_spec",
                  "ServingEngine._decode_body",
                  "ServingEngine._verify_body",
                  "ServingEngine._ingest_body"):
        assert entry in real, entry
    handlers = mxlint.JAX_FREE_ENTRIES["mxnet_tpu/serving/router.py"]
    for entry in ("_ReplicaHandler.do_GET", "_ReplicaHandler.do_POST",
                  "_RouterHandler.do_GET", "_RouterHandler.do_POST"):
        assert entry in handlers, entry


def test_verify_body_sync_flagged_and_clean_shape_passes(tmp_path):
    """A host readback inside the speculative verify trace body (or
    anything it reaches) is flagged; the real body's shape — pure
    NDArray math chained through helpers — is clean."""
    entries = {"mxnet_tpu/fixture.py": ("ServingEngine._verify_body",)}
    findings, _ = lint_src(tmp_path, """
        class ServingEngine:
            def _verify_body(self, nds):
                logits = self._chain(nds)
                return self._accept(logits)

            def _accept(self, logits):
                return logits[0].asnumpy()   # sync inside the trace body

            def _chain(self, nds):
                return nds
        """, hot_entries=entries)
    assert rules_of(findings) == ["hot-sync"]
    assert findings[0].context == "ServingEngine._accept"

    findings, _ = lint_src(tmp_path, """
        class ServingEngine:
            def _verify_body(self, nds):
                state = dict(zip(self._names, nds))
                logits, extra, pools = self._chain_logits(state)
                counts = self._accept(logits)
                return (counts,) + tuple(state.values())

            def _chain_logits(self, state):
                return state, state, state

            def _accept(self, logits):
                return logits
        """, hot_entries=entries)
    assert findings == []


def test_router_handler_jax_use_flagged(tmp_path):
    """A jax import (or device readback) reachable from the replica
    /generate handler is flagged — handlers must only submit and poll
    host-side stream flags; the engine-driver thread owns the device."""
    jax_free = {"mxnet_tpu/fixture.py": ("_ReplicaHandler.do_POST",)}
    findings, _ = _lint_jaxfree(tmp_path, """
        class _ReplicaHandler:
            def do_POST(self):
                import jax
                jax.block_until_ready(self.server.replica.engine._state)
        """, jax_free=jax_free)
    assert "jax-in-handler" in rules_of(findings)

    findings, _ = _lint_jaxfree(tmp_path, """
        import json
        import time

        class _ReplicaHandler:
            def do_POST(self):
                req = self.server.replica.submit(self._body())
                while not req.stream.finished:
                    time.sleep(0.002)
                self._send(200, json.dumps(list(req.stream)))

            def _body(self):
                return {}

            def _send(self, code, payload):
                pass
        """, jax_free=jax_free)
    assert findings == []


def test_tracez_handler_jax_use_flagged(tmp_path):
    """The router's /tracez handler (docs/OBSERVABILITY.md §Request
    tracing) is reachable from ``_RouterHandler.do_GET`` — it must stay
    a host-side rollup read: a jax touch on that path would block a
    trace scrape on the device."""
    jax_free = {"mxnet_tpu/fixture.py": ("_RouterHandler.do_GET",)}
    findings, _ = _lint_jaxfree(tmp_path, """
        class _RouterHandler:
            def do_GET(self):
                return self._send(200, self.server.router.tracez())
        """, jax_free=jax_free)
    assert findings == []

    findings, _ = _lint_jaxfree(tmp_path, """
        class _RouterHandler:
            def do_GET(self):
                return self._send(200, self.server.router.tracez())

            def _send(self, code, payload):
                import jax

                jax.block_until_ready(payload)
        """, jax_free=jax_free)
    assert "jax-in-handler" in rules_of(findings)


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "mxnet_tpu").mkdir(parents=True)
    (tmp_path / "mxnet_tpu" / "broken.py").write_text("def f(:\n")
    findings, _ = mxlint.run_lint([str(tmp_path / "mxnet_tpu")],
                                  root=str(tmp_path), hot_entries={},
                                  env_registry=set())
    assert rules_of(findings) == ["syntax-error"]


# ---------------------------------------------------------------------------
# jax-in-handler (metrics endpoint jax-free reachability)
# ---------------------------------------------------------------------------
JAXFREE = {"mxnet_tpu/fixture.py": ("Handler.do_GET",)}


def _lint_jaxfree(tmp_path, src, jax_free=None):
    path = tmp_path / "mxnet_tpu" / "fixture.py"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    findings, stats = mxlint.run_lint(
        [str(path)], root=str(tmp_path), hot_entries={},
        env_registry=frozenset(),
        jax_free_entries=jax_free if jax_free is not None else JAXFREE)
    return findings, stats


def test_jax_in_handler_inline_import_flagged(tmp_path):
    findings, _ = _lint_jaxfree(tmp_path, """
        class Handler:
            def do_GET(self):
                import jax

                return jax.devices()
        """)
    assert "jax-in-handler" in rules_of(findings)


def test_jax_in_handler_module_alias_use_flagged(tmp_path):
    # a module-level `import jax.numpy as jnp` USED in the handler is
    # the same defect as an inline import
    findings, _ = _lint_jaxfree(tmp_path, """
        import jax.numpy as jnp

        class Handler:
            def do_GET(self):
                return self._render()

            def _render(self):
                return jnp.zeros(3)
        """)
    assert "jax-in-handler" in rules_of(findings)
    assert any(f.context == "Handler._render" for f in findings)


def test_jax_in_handler_hot_sync_also_checked(tmp_path):
    # handler entries ride the hot-sync readback checks too: a scrape
    # must never block on a device value
    findings, _ = _lint_jaxfree(tmp_path, """
        class Handler:
            def do_GET(self):
                return self.loss.item()
        """)
    assert rules_of(findings) == ["hot-sync"]


def test_jax_free_handler_clean(tmp_path):
    findings, _ = _lint_jaxfree(tmp_path, """
        import json

        class Handler:
            def do_GET(self):
                return json.dumps(self._snapshot())

            def _snapshot(self):
                return {"ok": True}
        """)
    assert findings == []


def test_stale_jax_free_entry_is_a_finding(tmp_path):
    # renaming the handler must not silently un-lint the endpoint
    findings, _ = _lint_jaxfree(tmp_path, """
        class Handler:
            def do_GET_renamed(self):
                return 1
        """)
    assert rules_of(findings) == ["stale-hot-entry"]
    assert "Handler.do_GET" in findings[0].message


def test_metrics_server_entries_registered():
    """The REAL metrics_server handler is under the jax-free rule (and
    resolves — the full-tree gate below would flag stale-hot-entry if a
    refactor moved it without updating JAX_FREE_ENTRIES)."""
    real = mxlint.JAX_FREE_ENTRIES["mxnet_tpu/metrics_server.py"]
    assert "_Handler.do_GET" in real


# ---------------------------------------------------------------------------
# the tier-1 gate: the real tree is lint-clean, fast, at head
# ---------------------------------------------------------------------------
def test_plan_dispatch_entry_registered_and_rename_fails_loudly(tmp_path):
    """The unified Plan dispatch body is in the REAL HOT_PATH_ENTRIES
    (every strategy's every step funnels through it — a host sync there
    stalls dp, tp, pp, ring and ulysses at once), and renaming it in a
    fixture carrying the entry flags stale-hot-entry rather than
    silently un-linting the path."""
    real = mxlint.HOT_PATH_ENTRIES["mxnet_tpu/parallel/data_parallel.py"]
    assert "DataParallelStep._plan_dispatch" in real

    entries = {"mxnet_tpu/fixture.py": ("DataParallelStep._plan_dispatch",)}
    findings, _ = lint_src(tmp_path, """
        class DataParallelStep:
            def _plan_dispatch_renamed(self):
                return None
        """, hot_entries=entries)
    assert rules_of(findings) == ["stale-hot-entry"]
    assert "DataParallelStep._plan_dispatch" in findings[0].message

    # positive: a readback reachable from the dispatch body through a
    # helper (e.g. forcing the loss before returning) is flagged
    findings, _ = lint_src(tmp_path, """
        import numpy as np

        class DataParallelStep:
            def _plan_dispatch(self, fn, call_args):
                out = fn(*call_args)
                return self._force(out)

            def _force(self, out):
                return np.asarray(out)   # host sync in the hot funnel
        """, hot_entries=entries)
    assert rules_of(findings) == ["hot-sync"]
    assert findings[0].context == "DataParallelStep._force"

    # negative: the real body's shape — fault hook, scopes, dispatch —
    # carries no syncs
    findings, _ = lint_src(tmp_path, """
        class DataParallelStep:
            def _plan_dispatch(self, call_args, step_no, sp_active):
                self._on_dispatch(step_no)
                with self._scopes(sp_active):
                    return self._jitted(*call_args)

            def _scopes(self, sp_active):
                return sp_active

            def _on_dispatch(self, s):
                return s
        """, hot_entries=entries)
    assert findings == []


def test_full_tree_is_clean_and_fast():
    t0 = time.perf_counter()
    findings, stats = mxlint.run_lint()   # mxnet_tpu tools examples
    entries = mxlint.load_baseline(mxlint.DEFAULT_BASELINE)
    new, baselined, stale = mxlint.apply_baseline(findings, entries, _REPO)
    elapsed = time.perf_counter() - t0
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], (
        f"stale baseline entries (finding fixed? remove them): {stale}")
    # the 870s tier-1 budget is tight; the full pass must stay cheap on
    # this 2-vCPU box.  Budget sized for the box's documented 2-3x drift
    # (the SAME scan measured 4.5s-8.5s across three consecutive runs
    # while PR 8 landed) on a 157-file tree — the gate exists to catch an
    # mxlint pass going algorithmically slow, not to flake on a noisy
    # neighbor
    assert elapsed < 12.0, f"mxlint full tree took {elapsed:.1f}s"
    assert stats["files"] > 100, "scanner lost most of the tree"


def test_baseline_is_small_and_justified():
    entries = mxlint.load_baseline(mxlint.DEFAULT_BASELINE)
    assert len(entries) <= 15, "baseline is for ACCEPTED legacy findings"
    for e in entries:
        j = e.get("justification", "")
        assert j and not j.startswith("UNREVIEWED"), (
            f"baseline entry needs a reviewed one-line justification: {e}")


def test_every_rule_is_documented():
    doc = open(os.path.join(_REPO, "docs", "STATIC_ANALYSIS.md")).read()
    for rule in mxlint.RULES:
        assert rule in doc, f"rule {rule} missing from docs/STATIC_ANALYSIS.md"


# ---------------------------------------------------------------------------
# pass-outside-pipeline (PR 20: the pass-pipeline dispatch contract)
# ---------------------------------------------------------------------------
_PASS_FIXTURE_ENTRIES = {
    "mxnet_tpu/fixture.py": {
        "function": "_invoke_impl",
        "hook_module": "_pass_hooks",
        "allowed": (("_pass_hooks", "_OP_HOOKS"),),
    },
}


def test_pass_outside_pipeline_flags_smuggled_global(tmp_path):
    """The pre-PR-20 pattern — dispatch reading a precision module global
    directly instead of the pass-hook tuple — fires: a rewrite the
    pipeline fingerprint cannot see must not land silently."""
    findings, _ = lint_src(tmp_path, """
        from .passes import hooks as _pass_hooks
        from .precision import runtime as _precision

        def _invoke_impl(op, inputs):
            op_hooks = _pass_hooks._OP_HOOKS
            if _precision._AMP_POLICY is not None:
                inputs = [x.astype("bfloat16") for x in inputs]
            return op.fn(*inputs)
    """, rules=["pass-outside-pipeline"],
        pass_entries=_PASS_FIXTURE_ENTRIES)
    assert rules_of(findings) == ["pass-outside-pipeline"]
    assert "_precision._AMP_POLICY" in findings[0].message
    assert "GraphPass" in findings[0].message


def test_pass_outside_pipeline_clean_dispatch(tmp_path):
    """The sanctioned shape — ONE _OP_HOOKS read, locals/op attrs free —
    is clean; `x._data`-style loads on locals are not module globals."""
    findings, _ = lint_src(tmp_path, """
        from .passes import hooks as _pass_hooks

        def _invoke_impl(op, inputs):
            op_hooks = _pass_hooks._OP_HOOKS
            if op_hooks and inputs:
                for h in op_hooks:
                    inputs = h.rewrite_inputs(op.name, inputs)
            arrays = [x._data for x in inputs]
            return op.fn(*arrays)
    """, rules=["pass-outside-pipeline"],
        pass_entries=_PASS_FIXTURE_ENTRIES)
    assert findings == []


def test_pass_rule_stale_entry_fails_loudly(tmp_path):
    """A renamed dispatch body must not silently turn the rule into a
    no-op (the stale-hot-entry contract, applied here)."""
    findings, _ = lint_src(tmp_path, """
        from .passes import hooks as _pass_hooks

        def renamed_dispatch(op, inputs):
            return _pass_hooks._OP_HOOKS
    """, rules=["pass-outside-pipeline"],
        pass_entries=_PASS_FIXTURE_ENTRIES)
    assert rules_of(findings) == ["pass-outside-pipeline"]
    assert "does not resolve" in findings[0].message


def test_pass_rule_disconnected_hook_fails_loudly(tmp_path):
    """Deleting the _OP_HOOKS consultation disconnects the whole pass
    pipeline from dispatch — itself a finding."""
    findings, _ = lint_src(tmp_path, """
        from .passes import hooks as _pass_hooks

        def _invoke_impl(op, inputs):
            return op.fn(*inputs)
    """, rules=["pass-outside-pipeline"],
        pass_entries=_PASS_FIXTURE_ENTRIES)
    assert rules_of(findings) == ["pass-outside-pipeline"]
    assert "no longer consults" in findings[0].message


def test_pass_dispatch_entry_registered():
    """The real repo's consultation point is pinned, and the live tree
    is clean under the rule (the 0-findings gate covers it)."""
    cfg = mxlint.PASS_DISPATCH_ENTRIES["mxnet_tpu/ops/registry.py"]
    assert cfg["function"] == "_invoke_impl"
    assert cfg["hook_module"] == "_pass_hooks"
    assert ("_pass_hooks", "_OP_HOOKS") in cfg["allowed"]
