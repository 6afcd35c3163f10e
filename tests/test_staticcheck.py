"""The analyzer analyzed: mutation self-tests for scripts/staticcheck.

Contract (ISSUE 8 acceptance): every pass must TRIP on a seeded-bad
fixture and stay SILENT on its clean twin — a pass that can't catch
its own seeded violation is a false sense of security, and one that
flags the clean twin would train people to pragma reflexively. Plus
the pragma contract (reason required, unknown ids rejected, stale
pragmas flagged) and the whole-repo gate: the real tree must run
clean, which is what lets `make check` fail the build on a new
violation instead of a human noticing in review.

Fixtures are miniature repos (a `pkg/` package with the anchor-module
shape the passes key on), written to tmp_path — the analyzer's repo
detection is exercised for free.
"""

from __future__ import annotations

import textwrap

import pytest

from scripts.staticcheck.core import PASSES, _load_passes, run_repo

pytestmark = pytest.mark.staticcheck


def write_repo(tmp_path, files: dict[str, str]) -> str:
    for rel, content in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(content))
    return str(tmp_path)


def run_pass(tmp_path, files: dict[str, str], pass_id: str):
    root = write_repo(tmp_path, files)
    violations, pragma_errors, suppressed = run_repo(root, [pass_id])
    return violations, pragma_errors, suppressed


# A minimal package skeleton every fixture builds on (detection needs
# __init__.py plus a runtime/ or utils/ subdir).
BASE = {
    "pkg/__init__.py": "",
    "pkg/runtime/__init__.py": "",
    "pkg/utils/__init__.py": "",
}


# -- per-pass seeded-bad / clean-twin pairs ---------------------------

DONATION_BAD = {
    **BASE,
    "pkg/runtime/snap.py": """
        import numpy as np

        def snapshot(detector):
            return {
                k: np.asarray(v)
                for k, v in detector.state._asdict().items()
            }
    """,
}
DONATION_CLEAN = {
    **BASE,
    "pkg/runtime/snap.py": """
        import numpy as np

        def snapshot(pipe, detector):
            with pipe._dispatch_lock:
                return {
                    k: np.asarray(v)
                    for k, v in detector.state._asdict().items()
                }
    """,
}

KNOBS_CONFIG = """
    FOO_KNOBS = {
        "FOO_TIMEOUT_S": ("float", 1.0, "a registered knob"),
    }
    DEPLOYED_KNOB_REGISTRIES = ()
"""
KNOBS_BAD = {
    **BASE,
    "pkg/utils/config.py": KNOBS_CONFIG,
    "pkg/runtime/mod.py": """
        import os
        from os import getenv as g

        def f():
            a = os.environ.get("FOO_UNREGISTERED")
            b = g("ALSO_UNREGISTERED")     # aliased import can't dodge
            return a, b
    """,
}
KNOBS_CLEAN = {
    **BASE,
    "pkg/utils/config.py": KNOBS_CONFIG,
    "pkg/runtime/mod.py": """
        import os

        def f():
            return os.environ.get("FOO_TIMEOUT_S")
    """,
}

METRIC_BAD = {
    **BASE,
    "pkg/telemetry/__init__.py": "",
    "pkg/telemetry/metrics.py": """
        ANOMALY_GOOD = "anomaly_good_total"
        ANOMALY_DEAD = "anomaly_never_constructed_total"
    """,
    "pkg/telemetry/dashboards.py": """
        class Query:
            def __init__(self, kind, metric="", **kw):
                pass

        PANELS = [Query("rate", "anomaly_dangling_total")]
    """,
    "pkg/runtime/export.py": """
        def publish(registry):
            registry.counter_add("anomaly_inline_literal_total", 1.0)
    """,
}
METRIC_CLEAN = {
    **BASE,
    "pkg/telemetry/__init__.py": "",
    "pkg/telemetry/metrics.py": """
        ANOMALY_GOOD = "anomaly_good_total"
    """,
    "pkg/telemetry/dashboards.py": """
        class Query:
            def __init__(self, kind, metric="", **kw):
                pass

        PANELS = [Query("rate", "anomaly_good_total")]
    """,
    "pkg/runtime/export.py": """
        from ..telemetry import metrics as m

        def publish(registry):
            registry.counter_add(m.ANOMALY_GOOD, 1.0)
    """,
}

FRAME_BAD = {
    **BASE,
    "pkg/runtime/frame.py": "FRAME_MAGIC = b'OTDF'\n",
    "pkg/runtime/sneaky.py": """
        import struct
        from numpy import frombuffer as fb

        def decode(buf):
            header = struct.unpack("<I", buf[:4])
            return header, fb(buf[4:])
    """,
}
FRAME_CLEAN = {
    **BASE,
    "pkg/runtime/frame.py": """
        import struct
        import numpy as np

        def decode(buf):
            header = struct.unpack("<I", buf[:4])
            return header, np.frombuffer(buf[4:], np.uint8)
    """,
    "pkg/runtime/kafka_wire.py": """
        import struct

        def encode_len(n):
            return struct.pack(">i", n)
    """,
}

CONCURRENCY_BAD = {
    **BASE,
    "pkg/runtime/spawn.py": """
        import threading
        import time

        def leak(target, pipe):
            t = threading.Thread(target=target)
            t.start()
            with pipe._dispatch_lock:
                time.sleep(1.0)
    """,
}
CONCURRENCY_CLEAN = {
    **BASE,
    "pkg/runtime/spawn.py": """
        import threading
        import time

        def owned(target, pipe):
            t = threading.Thread(target=target)
            t.start()
            with pipe._dispatch_lock:
                snapshot = dict(pipe.stats)
            time.sleep(0.01)
            t.join()
            return snapshot

        def fire_and_forget(target):
            threading.Thread(target=target, daemon=True).start()
    """,
}

STATUS_BAD = {
    **BASE,
    "pkg/runtime/query.py": """
        import grpc

        class H:
            def answer(self):
                try:
                    self.dispatch()
                except Exception:
                    self.send_response(418)
                try:
                    self.teapot()
                except:
                    pass
                return grpc.StatusCode.FAILED_PRECONDITION
    """,
}
STATUS_CLEAN = {
    **BASE,
    "pkg/runtime/query.py": """
        import grpc

        class H:
            def answer(self):
                try:
                    self.dispatch()
                except Exception:  # noqa: BLE001 — handler must answer
                    self.send_response(503)
                try:
                    self.teapot()
                except ValueError:
                    pass
                return grpc.StatusCode.UNAVAILABLE
    """,
}

TRACE_BAD = {
    **BASE,
    "pkg/runtime/selftrace.py": """
        SPAN_BATCH = "detector.batch"
        SPAN_DISPATCH = "detector.dispatch"
        PHASE_DISPATCH = "dispatch"
        PHASE_ORPHAN = "orphan_phase"
    """,
    "pkg/runtime/mod.py": """
        from . import selftrace

        def f(trace, pool):
            trace.span("detector.rogue", 0.1)     # literal span name
            pool._phase("dispatch2", 0.1)         # literal phase label
            trace.span(selftrace.SPAN_DISPATCH, 0.1)
            pool._phase(selftrace.PHASE_DISPATCH, 0.1)
    """,
}
TRACE_CLEAN = {
    **BASE,
    "pkg/runtime/selftrace.py": """
        SPAN_BATCH = "detector.batch"
        SPAN_DISPATCH = "detector.dispatch"
        PHASE_DISPATCH = "dispatch"

        SPAN_FOR_PHASE = {PHASE_DISPATCH: SPAN_DISPATCH}

        def root_name():
            return SPAN_BATCH
    """,
    "pkg/runtime/mod.py": """
        from . import selftrace

        def f(trace, pool):
            trace.span(selftrace.SPAN_DISPATCH, 0.1)
            pool._phase(selftrace.PHASE_DISPATCH, 0.1)
    """,
}

PROVENANCE_BAD = {
    **BASE,
    "pkg/telemetry/__init__.py": "",
    "pkg/runtime/provenance.py": """
        HEAD_EWMA_Z = "ewma-z"
        HEAD_CUSUM = "cusum"
        REASON_LATENCY = "latency"
        REASON_ORPHAN = "never_referenced"

        HEAD_FOR_REASON = {REASON_LATENCY: HEAD_EWMA_Z}

        def heads_for(signals):
            return sorted({HEAD_FOR_REASON.get(s, HEAD_CUSUM)
                           for s in signals})
    """,
    "pkg/runtime/mod.py": """
        def event():
            return {
                "heads": ["ewma-z", "rogue-head"],   # unknown head kind
                "signals": ["latency", "made_up"],   # unknown signal
            }
    """,
    "pkg/telemetry/dashboards.py": """
        class Query:
            def __init__(self, kind, metric="", matchers=None, **kw):
                pass

        PANELS = [Query("rate", "anomaly_explanations_built_total",
                        matchers={"head": "unknown-head"})]
    """,
}
PROVENANCE_CLEAN = {
    **BASE,
    "pkg/telemetry/__init__.py": "",
    "pkg/runtime/provenance.py": """
        HEAD_EWMA_Z = "ewma-z"
        REASON_LATENCY = "latency"

        HEAD_FOR_REASON = {REASON_LATENCY: HEAD_EWMA_Z}
    """,
    "pkg/runtime/mod.py": """
        from .provenance import HEAD_EWMA_Z, REASON_LATENCY

        def event():
            return {
                "heads": [HEAD_EWMA_Z],
                "signals": ["latency"],   # declared value: spelling ok
                "head": "ewma-z",
            }
    """,
    "pkg/telemetry/dashboards.py": """
        class Query:
            def __init__(self, kind, metric="", matchers=None, **kw):
                pass

        PANELS = [Query("rate", "anomaly_explanations_built_total",
                        matchers={"head": "ewma-z"})]
    """,
}

EVICTION_BAD = {
    **BASE,
    "pkg/runtime/sweep.py": """
        def sweep(pipeline, names):
            tz = pipeline.tensorizer
            with pipeline._dispatch_lock:
                fold_and_zero(pipeline, names)
            # BUG: retirement escaped the critical section — a flush
            # can intern into the freed slot before the zero lands.
            return tz.retire_services(names)
    """,
}
EVICTION_CLEAN = {
    **BASE,
    "pkg/runtime/sweep.py": """
        def sweep(pipeline, names):
            tz = pipeline.tensorizer
            with pipeline._dispatch_lock:
                fold_and_zero(pipeline, names)
                freed = tz.retire_services(names)
            return freed
    """,
}

FIXTURES = [
    ("donation-race", DONATION_BAD, DONATION_CLEAN, 1),
    ("knob-discipline", KNOBS_BAD, KNOBS_CLEAN, 2),
    ("metric-surface", METRIC_BAD, METRIC_CLEAN, 3),
    ("frame-monopoly", FRAME_BAD, FRAME_CLEAN, 2),
    ("trace-discipline", TRACE_BAD, TRACE_CLEAN, 3),
    ("concurrency", CONCURRENCY_BAD, CONCURRENCY_CLEAN, 2),
    ("exception-status", STATUS_BAD, STATUS_CLEAN, 4),
    ("provenance-vocabulary", PROVENANCE_BAD, PROVENANCE_CLEAN, 4),
    ("eviction-lock", EVICTION_BAD, EVICTION_CLEAN, 1),
]


class TestMutationSelfTest:
    """Each pass trips on its seeded-bad fixture, is silent on the twin."""

    @pytest.mark.parametrize(
        "pass_id,bad,clean,min_hits",
        FIXTURES, ids=[f[0] for f in FIXTURES],
    )
    def test_bad_fixture_trips(self, tmp_path, pass_id, bad, clean, min_hits):
        violations, pragma_errors, _ = run_pass(tmp_path, bad, pass_id)
        assert len(violations) >= min_hits, (
            f"{pass_id} missed its seeded violations: {violations}"
        )
        assert all(v.pass_id == pass_id for v in violations)
        assert not pragma_errors

    @pytest.mark.parametrize(
        "pass_id,bad,clean,min_hits",
        FIXTURES, ids=[f[0] for f in FIXTURES],
    )
    def test_clean_twin_is_silent(self, tmp_path, pass_id, bad, clean, min_hits):
        violations, pragma_errors, _ = run_pass(tmp_path, clean, pass_id)
        assert violations == [], (
            f"{pass_id} false-positives on its clean twin: {violations}"
        )
        assert not pragma_errors


class TestPassDetails:
    def test_every_pass_has_a_fixture_pair(self):
        _load_passes()
        assert {f[0] for f in FIXTURES} == set(PASSES), (
            "a pass without a mutation self-test is unproven"
        )

    def test_donation_flags_unlocked_write(self, tmp_path):
        files = {
            **BASE,
            "pkg/runtime/hydrate.py": """
                def hydrate(detector, arrays):
                    detector.state = arrays
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "donation-race")
        assert len(violations) == 1 and "written" in violations[0].message

    # Registry-free config for the read-rule cases (a registered knob
    # nobody reads would trip the dead-knob rule — deliberately).
    EMPTY_CONFIG = "FOO_KNOBS = {}\nDEPLOYED_KNOB_REGISTRIES = ()\n"

    def test_knobs_helper_indirection_checked_at_call_site(self, tmp_path):
        files = {
            **BASE,
            "pkg/utils/config.py": self.EMPTY_CONFIG,
            "pkg/runtime/mod.py": """
                import os

                def read_env(name, default=""):
                    return os.environ.get(name, default)

                def f():
                    return read_env("NOT_REGISTERED")
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "knob-discipline")
        assert len(violations) == 1
        assert "NOT_REGISTERED" in violations[0].message
        assert "read_env" in violations[0].message

    def test_knobs_env_writes_and_passthrough_allowed(self, tmp_path):
        files = {
            **BASE,
            "pkg/utils/config.py": self.EMPTY_CONFIG,
            "pkg/runtime/mod.py": """
                import os

                def f():
                    os.environ["ANYTHING"] = "1"
                    os.environ.setdefault("ANYTHING_ELSE", "cpu")
                    return dict(os.environ)
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "knob-discipline")
        assert violations == []

    def test_knobs_dead_knob_detected(self, tmp_path):
        files = {
            **BASE,
            "pkg/utils/config.py": """
                FOO_KNOBS = {
                    "FOO_NOBODY_READS": ("int", 1, "dead"),
                }
                DEPLOYED_KNOB_REGISTRIES = ()
            """,
            "pkg/runtime/mod.py": "X = 1\n",
        }
        violations, _, _ = run_pass(tmp_path, files, "knob-discipline")
        assert len(violations) == 1 and "dead" in violations[0].message

    def test_knobs_deployed_registry_must_thread(self, tmp_path):
        files = {
            **BASE,
            "pkg/utils/config.py": """
                BAR_KNOBS = {
                    "BAR_PORT": ("int", 1, "deployed but unthreaded"),
                }
                DEPLOYED_KNOB_REGISTRIES = ("BAR_KNOBS",)
            """,
            "pkg/runtime/daemon.py": "X = 1\n",
            "pkg/utils/k8s.py": "Y = 2\n",
            "deploy/docker-compose.anomaly.yml": "services: {}\n",
            "pkg/runtime/mod.py": """
                import os
                USED = os.environ.get("BAR_PORT")
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "knob-discipline")
        msgs = "\n".join(v.message for v in violations)
        assert "daemon.py" in msgs and "compose" in msgs
        assert "k8s generator" in msgs

    def test_frame_import_alias_cannot_dodge(self, tmp_path):
        files = {
            **BASE,
            "pkg/runtime/frame.py": "",
            "pkg/runtime/dodge.py": """
                import numpy as definitely_not_numpy

                def sneak(b):
                    return definitely_not_numpy.frombuffer(b)
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "frame-monopoly")
        assert len(violations) == 1
        assert "numpy.frombuffer" in violations[0].message

    def test_concurrency_str_join_does_not_satisfy_ownership(self, tmp_path):
        """A log-formatting `", ".join(...)` (or os.path.join) in the
        owning class must NOT count as joining the thread."""
        files = {
            **BASE,
            "pkg/runtime/leaky.py": """
                import os
                import threading

                class C:
                    def start(self, target):
                        self._t = threading.Thread(target=target)
                        self._t.start()

                    def describe(self, parts):
                        return ", ".join(parts) + os.path.join("a", "b")
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "concurrency")
        assert len(violations) == 1 and "non-daemon" in violations[0].message

    def test_concurrency_real_join_in_class_satisfies_ownership(self, tmp_path):
        files = {
            **BASE,
            "pkg/runtime/owned.py": """
                import threading

                class C:
                    def start(self, target):
                        self._t = threading.Thread(target=target)
                        self._t.start()

                    def stop(self):
                        self._t.join(timeout=5.0)
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "concurrency")
        assert violations == []

    def test_knobs_compose_prefix_knob_not_fooled(self, tmp_path):
        """ANOMALY_CHECKPOINT missing from compose must be flagged even
        while ANOMALY_CHECKPOINT_INTERVAL_S (a superstring) is present
        — and a mention in a comment must not count as threading."""
        files = {
            **BASE,
            "pkg/utils/config.py": """
                BAR_KNOBS = {
                    "BAR_CHECKPOINT": ("str", "", "prefix knob"),
                    "BAR_CHECKPOINT_INTERVAL_S": ("float", 30.0, "superstring"),
                }
                DEPLOYED_KNOB_REGISTRIES = ("BAR_KNOBS",)
            """,
            "pkg/runtime/daemon.py": """
                USED = ("BAR_CHECKPOINT", "BAR_CHECKPOINT_INTERVAL_S")
            """,
            "pkg/utils/k8s.py": "from .config import BAR_KNOBS\n",
            "deploy/docker-compose.anomaly.yml": (
                "services:\n"
                "  d:\n"
                "    environment:\n"
                "      # BAR_CHECKPOINT only mentioned in this comment\n"
                "      - BAR_CHECKPOINT_INTERVAL_S=30.0\n"
            ),
        }
        violations, _, _ = run_pass(tmp_path, files, "knob-discipline")
        assert len(violations) == 1
        assert "BAR_CHECKPOINT'" in violations[0].message
        assert "compose" in violations[0].message

    def test_status_vocabulary_literal_and_assignment(self, tmp_path):
        files = {
            **BASE,
            "pkg/runtime/otlp.py": """
                class H:
                    def do_POST(self):
                        status = 419
                        self.send_response(status)
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "exception-status")
        assert len(violations) == 1 and "419" in violations[0].message

    def test_broad_except_pragma_suppresses_not_stale(self, tmp_path):
        """The pass's own documented suppression path: a staticcheck
        pragma on the except line is NOT a free-text justification —
        the violation is emitted and the pragma consumes it, instead
        of the pragma short-circuiting the finding and then being
        reported stale."""
        files = {
            **BASE,
            "pkg/runtime/loop.py": """
                def pump():
                    try:
                        step()
                    except Exception:  # staticcheck: ok[exception-status] sender loop must survive poison frames
                        pass
            """,
        }
        violations, pragma_errors, suppressed = run_pass(
            tmp_path, files, "exception-status"
        )
        assert violations == [] and pragma_errors == []
        assert suppressed == 1

    def test_broad_except_string_hash_is_not_a_reason(self, tmp_path):
        """A ``#`` inside a string literal on the handler's first line
        must not satisfy the justification requirement."""
        files = {
            **BASE,
            "pkg/runtime/loop.py": """
                def pump():
                    try:
                        step()
                    except Exception:
                        log("color #fff")
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "exception-status")
        assert len(violations) == 1
        assert "no stated reason" in violations[0].message

    def test_broad_except_bare_lint_marker_is_not_a_reason(self, tmp_path):
        """Content-free markers (`# noqa`, `# type: ignore`) wave off
        other linters but say nothing about WHY the catch-all is right
        — they must not satisfy the justification requirement, while
        the repo's `# noqa: BLE001 — why` convention (text after the
        directive) still does."""
        files = {
            **BASE,
            "pkg/runtime/loop.py": """
                def pump():
                    try:
                        step()
                    except Exception:  # noqa
                        pass
                def pump2():
                    try:
                        step()
                    except Exception:  # type: ignore
                        pass
                def pump3():
                    try:
                        step()
                    except Exception:  # noqa: BLE001 — poison frame must not kill the pump
                        pass
            """,
        }
        violations, _, _ = run_pass(tmp_path, files, "exception-status")
        assert len(violations) == 2
        assert all(v.line in (5, 10) for v in violations)


class TestPragmaContract:
    BAD_LINE = """
        def snapshot(detector):
            return detector.state{pragma}
    """

    def _repo(self, pragma: str) -> dict[str, str]:
        return {
            **BASE,
            "pkg/runtime/snap.py": self.BAD_LINE.format(pragma=pragma),
        }

    def test_pragma_with_reason_suppresses(self, tmp_path):
        files = self._repo(
            "  # staticcheck: ok[donation-race] caller quiesced the "
            "pipeline first"
        )
        violations, pragma_errors, suppressed = run_pass(
            tmp_path, files, "donation-race"
        )
        assert violations == [] and pragma_errors == []
        assert suppressed == 1

    def test_pragma_requires_reason(self, tmp_path):
        files = self._repo("  # staticcheck: ok[donation-race]")
        violations, pragma_errors, _ = run_pass(
            tmp_path, files, "donation-race"
        )
        # The violation STANDS and the reasonless pragma is flagged.
        assert len(violations) == 1
        assert any("no reason" in e.message for e in pragma_errors)

    def test_pragma_unknown_pass_id_rejected(self, tmp_path):
        files = self._repo(
            "  # staticcheck: ok[not-a-pass] because reasons"
        )
        violations, pragma_errors, _ = run_pass(
            tmp_path, files, "donation-race"
        )
        assert len(violations) == 1
        assert any("unknown pass id" in e.message for e in pragma_errors)

    def test_stale_pragma_flagged(self, tmp_path):
        files = {
            **BASE,
            "pkg/runtime/snap.py": (
                "X = 1  # staticcheck: ok[donation-race] nothing here "
                "needs suppressing\n"
            ),
        }
        _violations, pragma_errors, _ = run_pass(
            tmp_path, files, "donation-race"
        )
        assert any("suppresses nothing" in e.message for e in pragma_errors)

    def test_pragma_shaped_string_literal_is_not_a_pragma(self, tmp_path):
        """Pragmas are harvested from real comments (tokenizer) — a
        string literal that merely LOOKS like one neither suppresses a
        violation on its line nor trips the stale-pragma error."""
        files = {
            **BASE,
            "pkg/runtime/snap.py": (
                'BANNER = "# staticcheck: ok[donation-race] not a '
                'pragma"\n'
                "def snapshot(detector):\n"
                "    return detector.state  # comment, not a pragma\n"
            ),
        }
        violations, pragma_errors, suppressed = run_pass(
            tmp_path, files, "donation-race"
        )
        assert pragma_errors == [] and suppressed == 0
        assert len(violations) == 1 and violations[0].line == 3

    def test_pragma_for_unselected_pass_ignored(self, tmp_path):
        files = self._repo(
            "  # staticcheck: ok[donation-race] caller quiesced"
        )
        _violations, pragma_errors, _ = run_pass(
            tmp_path, files, "frame-monopoly"
        )
        assert pragma_errors == []


class TestWholeRepo:
    def test_repo_runs_clean(self):
        """THE gate: zero unsuppressed violations on the real tree,
        every suppression carrying a reason (make check enforces the
        same thing; this keeps it true under plain pytest too)."""
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        violations, pragma_errors, _suppressed = run_repo(root)
        rendered = "\n".join(
            v.render() for v in violations + pragma_errors
        )
        assert not violations and not pragma_errors, (
            f"staticcheck violations in the repo:\n{rendered}"
        )

    def test_runs_fast_without_jax(self):
        """The <10s / no-jax contract that lets make check stay cheap:
        the analyzer package must not import jax/numpy (pure ast), and
        a full-repo run must finish inside the budget.

        The import ban is checked by AST over the package's own source
        — ``import numpy as np`` binds the name ``np``, so a
        sys.modules/__dict__ scan for the literal string 'numpy' would
        miss the repo's universal spelling."""
        import ast
        import glob
        import os
        import time

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg = os.path.join(root, "scripts", "staticcheck")
        banned = {"jax", "numpy"}
        for path in glob.glob(
            os.path.join(pkg, "**", "*.py"), recursive=True
        ):
            tree = ast.parse(open(path, encoding="utf-8").read())
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.module:
                    mods = [node.module]
                for mod in mods:
                    assert mod.split(".")[0] not in banned, (
                        f"{os.path.relpath(path, root)}:{node.lineno} "
                        f"imports {mod} — staticcheck must stay pure-ast"
                    )

        # CPU time, not wall clock: the suite shares its box with
        # other runs, and a neighbor's load must not flake this — a
        # sneaked-in heavy import or quadratic pass still shows up.
        start = time.process_time()
        run_repo(root)
        elapsed = time.process_time() - start
        assert elapsed < 10.0, (
            f"whole-repo staticcheck burned {elapsed:.1f}s CPU — the "
            "make check budget is <10s"
        )
