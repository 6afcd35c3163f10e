"""Repo invariants checker: the internal/tools sanitycheck analogue.

The reference keeps a tooling dir with lint pins and a sanity script
(/root/reference/internal/tools/, Makefile `check` target :93-96). This
framework's equivalent checks the contracts the driver and judge rely
on, without importing jax (fast, no device):

  - bench.py exists and its contract (ONE json line with
    metric/value/unit/vs_baseline) is declared in code;
  - __graft_entry__ exposes entry() and dryrun_multichip();
  - every tracetesting suite parses and targets a known service dir;
  - proto/demo.proto compiles if protoc is available;
  - deploy/k8s manifests parse as YAML k8s objects;
  - overload-protection invariants hold statically: the pipeline's
    shed-lane contract excludes the error lane, the bounded-admission
    suite asserts the budget and zero-error-lane-shed invariants, and
    every OVERLOAD_KNOBS env knob is threaded through the daemon, the
    compose overlay and the k8s generator;
  - no Python file accidentally imports from /root/reference.

Run via `make check`.
"""

from __future__ import annotations

import ast
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAILS: list[str] = []


def check(ok: bool, msg: str) -> None:
    print(("ok   " if ok else "FAIL ") + msg)
    if not ok:
        FAILS.append(msg)


def main() -> int:
    # bench contract
    bench = os.path.join(ROOT, "bench.py")
    check(os.path.exists(bench), "bench.py exists")
    if os.path.exists(bench):
        src = open(bench).read()
        for key in ('"metric"', '"value"', '"unit"', '"vs_baseline"'):
            check(key in src, f"bench.py emits {key}")

    # graft entry contract
    entry_path = os.path.join(ROOT, "__graft_entry__.py")
    check(os.path.exists(entry_path), "__graft_entry__.py exists")
    if os.path.exists(entry_path):
        tree = ast.parse(open(entry_path).read())
        fns = {n.name for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)}
        check("entry" in fns, "__graft_entry__.entry defined")
        check("dryrun_multichip" in fns, "__graft_entry__.dryrun_multichip defined")

    # tracetesting suites parse and target known services
    import yaml

    # Service names as the suites spell them (dirs use the reference's
    # kebab-case; the services package registers the same names on its
    # classes).
    known_services = {
        "ad", "cart", "checkout", "currency", "email", "frontend",
        "payment", "product-catalog", "quote", "recommendation",
        "shipping", "fraud-detection", "accounting",
        # Cross-cutting suites beyond the per-service set: the edge
        # observability surfaces (/jaeger + /grafana).
        "observability",
    }
    tdir = os.path.join(ROOT, "tracetesting")
    suites = sorted(os.listdir(tdir)) if os.path.isdir(tdir) else []
    check(len(suites) >= 10, f"tracetesting covers {len(suites)} services (>=10)")
    for svc in suites:
        check(svc in known_services, f"tracetesting/{svc} targets a known service")
        for fname in os.listdir(os.path.join(tdir, svc)):
            path = os.path.join(tdir, svc, fname)
            try:
                docs = list(yaml.safe_load_all(open(path)))
                check(all(d for d in docs), f"tracetesting/{svc}/{fname} parses")
            except yaml.YAMLError as e:
                check(False, f"tracetesting/{svc}/{fname} parses ({e})")

    # proto compiles
    if shutil.which("protoc"):
        r = subprocess.run(
            ["protoc", "--python_out", "/tmp", "proto/demo.proto"],
            cwd=ROOT, capture_output=True,
        )
        check(r.returncode == 0, "proto/demo.proto compiles")
    else:
        print("skip proto (no protoc)")

    # k8s manifests parse (aggregates + the per-component breakout dir)
    kdir = os.path.join(ROOT, "deploy", "k8s")
    check(os.path.isdir(kdir), "deploy/k8s exists")
    manifest_paths = []
    for dirpath, _dirs, files in os.walk(kdir):
        manifest_paths += [
            os.path.join(dirpath, f) for f in files if f.endswith(".yaml")
        ]
    for path in sorted(manifest_paths):
        docs = list(yaml.safe_load_all(open(path)))
        rel = os.path.relpath(path, ROOT)
        check(
            all(d and "apiVersion" in d and "kind" in d for d in docs),
            f"{rel} is valid k8s YAML",
        )

    # overload-protection invariants (all static — no jax import):
    # 1) the shed-lane contract in runtime/pipeline.py must exclude the
    #    error lane (SHED_LANES is the pinned constant);
    pipeline_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "pipeline.py"
    )
    shed_lanes = None
    for node in ast.walk(ast.parse(open(pipeline_py).read())):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "SHED_LANES"
            for t in node.targets
        ):
            shed_lanes = ast.literal_eval(node.value)
    check(shed_lanes is not None, "pipeline.py declares SHED_LANES")
    check(
        shed_lanes is not None and "error" not in shed_lanes,
        "shed policy never touches the error lane (SHED_LANES)",
    )
    check(
        "queue_max_rows" in open(pipeline_py).read(),
        "pipeline.py implements the bounded pending-queue budget",
    )
    # 2) the overload suite asserts the budget bound and the
    #    zero-error-lane-shed counters (the runtime proof of #1);
    overload_tests = os.path.join(ROOT, "tests", "test_overload.py")
    check(os.path.exists(overload_tests), "tests/test_overload.py exists")
    if os.path.exists(overload_tests):
        tsrc = open(overload_tests).read()
        check(
            "pending_rows() <= pipe.queue_max_rows" in tsrc,
            "overload suite asserts the pending-queue bound",
        )
        check(
            'shed_rows["error"] == 0' in tsrc,
            "overload suite asserts zero error-lane shed",
        )
    # 3) every overload AND ingest-pool knob (utils/config.py
    #    OVERLOAD_KNOBS / INGEST_KNOBS — read via AST, importing would
    #    pull jax) reaches the daemon, the compose overlay and the k8s
    #    generator: one registry per knob family, no drift.
    config_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "utils", "config.py"
    )
    registries: dict[str, dict] = {}
    for node in ast.walk(ast.parse(open(config_py).read())):
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if (
                    isinstance(t, ast.Name)
                    and t.id in (
                        "DAEMON_KNOBS", "OVERLOAD_KNOBS", "INGEST_KNOBS",
                        "REPLICATION_KNOBS", "FRAME_KNOBS",
                        "QUERY_KNOBS", "SPINE_KNOBS", "SELFTRACE_KNOBS",
                        "HISTORY_KNOBS", "REMEDIATION_KNOBS",
                        "FLEET_KNOBS", "AUTOSCALE_KNOBS",
                        "SHADOW_KNOBS", "PROVENANCE_KNOBS",
                        "FRONTDOOR_KNOBS", "KEYSPACE_KNOBS",
                    )
                    and node.value is not None
                ):
                    registries[t.id] = ast.literal_eval(node.value)
    for reg_name in (
        "DAEMON_KNOBS", "OVERLOAD_KNOBS", "INGEST_KNOBS",
        "REPLICATION_KNOBS", "FRAME_KNOBS", "QUERY_KNOBS",
        "SPINE_KNOBS", "SELFTRACE_KNOBS", "HISTORY_KNOBS",
        "REMEDIATION_KNOBS", "FLEET_KNOBS", "AUTOSCALE_KNOBS",
        "SHADOW_KNOBS", "PROVENANCE_KNOBS", "FRONTDOOR_KNOBS",
        "KEYSPACE_KNOBS",
    ):
        knobs = registries.get(reg_name)
        check(bool(knobs), f"utils/config.py declares {reg_name}")
        for consumer in (
            os.path.join("opentelemetry_demo_tpu", "runtime", "daemon.py"),
            os.path.join("deploy", "docker-compose.anomaly.yml"),
            os.path.join("opentelemetry_demo_tpu", "utils", "k8s.py"),
        ):
            text = open(os.path.join(ROOT, consumer)).read()
            if consumer.endswith("k8s.py"):
                # k8s.py consumes the registry itself — the reference
                # must be the import, not copied strings.
                check(
                    reg_name in text,
                    f"{consumer} consumes the {reg_name} registry",
                )
                continue
            for knob in knobs or ():
                check(knob in text, f"{consumer} threads {knob}")
    # The generated manifests actually carry the knob env (the
    # generator could consume the registry and still drop the env
    # block): spot-check the sidecar bundle.
    sidecar = os.path.join(ROOT, "deploy", "k8s", "anomaly-detector-sidecar.yaml")
    if os.path.exists(sidecar):
        stext = open(sidecar).read()
        for knobs in registries.values():
            for knob in knobs:
                check(knob in stext, f"deploy/k8s sidecar carries {knob}")
    # 4) ingest-pool invariants: the pool queue is bounded (no
    #    unbounded buffer ahead of the pipeline's admission), and the
    #    pooled path proves bit-exactness + no-aliasing in tests.
    pool_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "ingest_pool.py"
    )
    check(os.path.exists(pool_py), "runtime/ingest_pool.py exists")
    if os.path.exists(pool_py):
        ptext = open(pool_py).read()
        check(
            "IngestPoolSaturated" in ptext and "max_pending" in ptext,
            "ingest pool bounds its request queue (IngestPoolSaturated)",
        )
    pool_tests = os.path.join(ROOT, "tests", "test_ingest_pool.py")
    check(os.path.exists(pool_tests), "tests/test_ingest_pool.py exists")
    if os.path.exists(pool_tests):
        ttext = open(pool_tests).read()
        for marker in (
            "test_pooled_bit_exact",
            "test_scratch_reuse_no_aliasing",
            "test_native_decode_releases_gil",
        ):
            check(marker in ttext, f"ingest-pool suite pins {marker}")

    # 5) hot-standby replication invariants: both deploy surfaces
    #    define the standby service (a replication layer nobody can
    #    deploy is dead code), and the suite pins the fencing +
    #    anti-entropy proofs.
    compose_text = open(
        os.path.join(ROOT, "deploy", "docker-compose.anomaly.yml")
    ).read()
    check(
        "anomaly-detector-standby:" in compose_text,
        "compose overlay defines the anomaly-detector-standby service",
    )
    check(
        "ANOMALY_ROLE=standby" in compose_text,
        "compose standby service runs ANOMALY_ROLE=standby",
    )
    k8s_text = open(
        os.path.join(ROOT, "opentelemetry_demo_tpu", "utils", "k8s.py")
    ).read()
    check(
        "anomaly-detector-standby" in k8s_text,
        "k8s generator emits the anomaly-detector-standby deployment",
    )
    if os.path.exists(sidecar):
        check(
            "anomaly-detector-standby" in open(sidecar).read(),
            "deploy/k8s sidecar bundle carries the standby deployment",
        )
    repl_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "replication.py"
    )
    check(os.path.exists(repl_py), "runtime/replication.py exists")
    repl_tests = os.path.join(ROOT, "tests", "test_replication.py")
    check(os.path.exists(repl_tests), "tests/test_replication.py exists")
    if os.path.exists(repl_tests):
        rtext = open(repl_tests).read()
        for marker in (
            "test_stale_primary_fenced_on_all_three_paths",
            "test_blackholed_standby_converges_by_merge",
            "test_failover_drill_sigkill_primary",
        ):
            check(marker in rtext, f"replication suite pins {marker}")

    # 6) ONE verified wire format (runtime/frame.py): the checksummed
    #    columnar frame is the single source of truth for every state
    #    byte layout — ingest scratch→pipeline, replication payloads,
    #    checkpoint files. The byte-primitive monopoly itself
    #    (np.savez/np.load/np.frombuffer/struct.pack fenced to the
    #    layout owners) is DELEGATED to scripts/staticcheck's
    #    frame-monopoly pass — an AST import-resolution check a renamed
    #    import can't dodge, and one implementation so sanitycheck and
    #    staticcheck can never disagree. The literal pins kept here are
    #    the frame module's own contract markers.
    frame_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "frame.py"
    )
    check(os.path.exists(frame_py), "runtime/frame.py exists")
    if os.path.exists(frame_py):
        ftext = open(frame_py).read()
        for marker in ("FRAME_MAGIC", "FRAME_VERSION", "def encode",
                       "def decode", "crc32c"):
            check(marker in ftext, f"runtime/frame.py declares {marker}")
    if os.environ.get("SANITYCHECK_SKIP_STATICCHECK") == "1":
        # make check just ran the FULL staticcheck (frame-monopoly
        # included) in the previous step — re-running the delegated
        # pass here would parse the whole tree a second time for no
        # new information. Standalone sanitycheck runs still delegate.
        check(True, "frame monopoly delegated (staticcheck already ran)")
    else:
        sys.path.insert(0, ROOT)
        from scripts.staticcheck.core import run_repo as _staticcheck_run

        frame_violations, frame_pragma_errs, _supp = _staticcheck_run(
            ROOT, select=["frame-monopoly"]
        )
        # Pragma misuse (reasonless/stale/unknown-id) fails HERE too,
        # not only under `python -m scripts.staticcheck` — delegation
        # means sanitycheck and staticcheck cannot disagree.
        frame_problems = frame_violations + frame_pragma_errs
        check(
            not frame_problems,
            "frame monopoly holds (staticcheck frame-monopoly pass) "
            f"{[v.render() for v in frame_problems] or ''}",
        )
    frame_tests = os.path.join(ROOT, "tests", "test_frame.py")
    check(os.path.exists(frame_tests), "tests/test_frame.py exists")
    if os.path.exists(frame_tests):
        fttext = open(frame_tests).read()
        for marker in (
            "test_every_single_bit_flip_is_caught",
            "test_corrupt_link_quarantines_and_converges",
            "test_checkpoint_v0_npz_migrates",
            "test_truncated_trailer_quarantined",
        ):
            check(marker in fttext, f"frame suite pins {marker}")

    # 7) live query plane (runtime/query.py): reads over live sketch
    #    state happen ONLY through the role-dispatched snapshot helper
    #    (live dispatch DONATES the detector's device buffers — a
    #    direct read races "Array has been deleted", and a forked read
    #    path would break the primary/replica bit-consistency
    #    contract). Pinned grep-level, same style as the frame.py
    #    np.frombuffer pin:
    #    a) query.py consumes a snapshot_fn and NEVER names the
    #       detector state or the dispatch lock;
    #    b) the daemon wires the engine to its snapshot helper;
    #    c) the suite pins the failover/consistency/exemplar proofs.
    query_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "query.py"
    )
    check(os.path.exists(query_py), "runtime/query.py exists")
    if os.path.exists(query_py):
        qtext = open(query_py).read()
        for marker in (
            "class QueryEngine", "class QueryService", "snapshot_fn",
            "def dispatch", "/search", "/annotations", "/query/flight",
        ):
            check(marker in qtext, f"runtime/query.py declares {marker!r}")
        check(
            "detector.state" not in qtext and "_dispatch_lock" not in qtext,
            "query.py reads state only via the snapshot helper "
            "(no detector.state / _dispatch_lock reference)",
        )
    daemon_text = open(os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "daemon.py"
    )).read()
    check(
        "def _query_snapshot" in daemon_text
        and "snapshot_fn=self._query_snapshot" in daemon_text,
        "daemon wires the query engine to the role-dispatched "
        "snapshot helper",
    )
    query_tests = os.path.join(ROOT, "tests", "test_query.py")
    check(os.path.exists(query_tests), "tests/test_query.py exists")
    if os.path.exists(query_tests):
        qttext = open(query_tests).read()
        for marker in (
            "test_read_replica_survives_primary_sigkill",
            "test_replica_answers_bit_identical_at_same_seq",
            "test_exemplars_round_trip_to_ingested_traces",
            "test_queries_never_race_dispatch_donation",
            "test_grafana_datasource_contract",
        ):
            check(marker in qttext, f"query suite pins {marker}")

    # 8) detector self-telemetry (runtime/selftrace.py +
    #    runtime/flightrec.py): the span/phase vocabulary is declared
    #    (the trace-discipline staticcheck pass polices its use), the
    #    tracer samples deterministically, the flight recorder dumps
    #    evidence, and the suite pins the proofs.
    selftrace_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "selftrace.py"
    )
    check(os.path.exists(selftrace_py), "runtime/selftrace.py exists")
    if os.path.exists(selftrace_py):
        sttext = open(selftrace_py).read()
        for marker in (
            "class SelfTracer", "class BatchTrace", "def splitmix64",
            "def sampled", "SPAN_BATCH", "SPAN_FLAG", "PHASE_DECODE",
            "def encode_selftrace_request", "def decode_selftrace_request",
        ):
            check(marker in sttext, f"runtime/selftrace.py declares {marker}")
    flight_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "flightrec.py"
    )
    check(os.path.exists(flight_py), "runtime/flightrec.py exists")
    if os.path.exists(flight_py):
        fltext = open(flight_py).read()
        for marker in ("class FlightRecorder", "def record", "def dump"):
            check(marker in fltext, f"runtime/flightrec.py declares {marker}")
    selftrace_tests = os.path.join(ROOT, "tests", "test_selftrace.py")
    check(os.path.exists(selftrace_tests), "tests/test_selftrace.py exists")
    if os.path.exists(selftrace_tests):
        stt = open(selftrace_tests).read()
        for marker in (
            "test_span_parent_and_links_round_trip",
            "test_sampling_is_deterministic",
            "test_flight_ring_is_bounded",
            "test_dump_on_saturated_transition",
            "test_phase_histograms_on_metrics",
            "test_selftrace_overhead_canary",
        ):
            check(marker in stt, f"selftrace suite pins {marker}")

    # 9) time-travel tier (runtime/history.py + runtime/replaybench.py):
    #    the frame-native history store is the ONLY frame consumer
    #    outside the live path. Pinned structurally: an AST scan of the
    #    package's import statements must find `frame` imported by
    #    EXACTLY the live-path owners (ingest scratch→pipeline,
    #    replication link, checkpoint file, the daemon's boot-time
    #    frame.configure) plus history.py — a sixth importer is a new
    #    frame consumer nobody reviewed. Plus the subsystem's own
    #    contract markers, the replay/requires_env marker registrations,
    #    and the suite pins.
    history_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "history.py"
    )
    check(os.path.exists(history_py), "runtime/history.py exists")
    if os.path.exists(history_py):
        htext = open(history_py).read()
        for marker in (
            "class HistoryStore", "class HistoryWriter",
            "class HistoryReader", "def merge_record_arrays",
            "RECORD_MAGIC", "fence.check", "quarantine",
        ):
            check(marker in htext, f"runtime/history.py declares {marker}")
    frame_importers: set[str] = set()
    pkg_root = os.path.join(ROOT, "opentelemetry_demo_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            fpath = os.path.join(dirpath, fname)
            try:
                tree = ast.parse(open(fpath).read())
            except SyntaxError:
                continue
            for node in ast.walk(tree):
                names = []
                if isinstance(node, ast.ImportFrom):
                    if node.module and node.module.split(".")[-1] == "frame":
                        names = ["frame"]
                    else:
                        names = [a.name for a in node.names]
                elif isinstance(node, ast.Import):
                    names = [a.name.split(".")[-1] for a in node.names]
                if "frame" in names:
                    frame_importers.add(
                        os.path.relpath(fpath, pkg_root).replace(os.sep, "/")
                    )
    expected_frame_importers = {
        "runtime/checkpoint.py",   # frames ON DISK (live durability)
        "runtime/daemon.py",       # boot-time frame.configure()
        "runtime/ingest_pool.py",  # scratch→pipeline hop (live)
        "runtime/replication.py",  # primary→standby payloads (live)
        "runtime/history.py",      # THE one consumer outside the live path
    }
    check(
        frame_importers == expected_frame_importers,
        "history.py is the only frame consumer outside the live path "
        f"(importers {sorted(frame_importers)})",
    )
    replaybench_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "replaybench.py"
    )
    check(os.path.exists(replaybench_py), "runtime/replaybench.py exists")
    check(
        "replaybench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has a replaybench target",
    )
    pyproject = open(os.path.join(ROOT, "pyproject.toml")).read()
    for marker_name in ("history:", "replay:", "requires_env(resource):"):
        check(
            marker_name in pyproject,
            f"pyproject registers the {marker_name.rstrip(':')} marker",
        )
    ttext = open(os.path.join(ROOT, "tests", "test_tracetest.py")).read()
    check(
        "requires_env" in ttext,
        "tests/test_tracetest.py carries the requires_env marker "
        "(its failures are env gaps, not regressions)",
    )
    history_tests = os.path.join(ROOT, "tests", "test_history.py")
    check(os.path.exists(history_tests), "tests/test_history.py exists")
    if os.path.exists(history_tests):
        httext = open(history_tests).read()
        for marker in (
            "test_ladder_fold_bit_identical_to_direct_merge",
            "test_corrupt_record_quarantined_and_skipped",
            "test_stale_writer_append_refused",
            "test_range_queries_serve_from_disk",
            "test_replay_verdicts_bit_identical",
            "test_grafana_range_honored",
        ):
            check(marker in httext, f"history suite pins {marker}")

    # 10) closed-loop auto-mitigation (runtime/remediation.py): the
    #     controller exists with its guardrail surface, auto-mitigation
    #     defaults OFF (opt-in is a hard product decision, not a knob
    #     default someone can drift), the FLAG-WRITER MONOPOLY holds —
    #     the atomic flag-file write primitive (flags.atomic_write_doc)
    #     is imported by EXACTLY the flag editor UI and the remediation
    #     actuator (an AST import scan, closed set, same discipline as
    #     the frame-importer pin: a third flag writer is a reviewed
    #     decision, not drift) — and the chaos suite pins the proofs.
    remediation_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "remediation.py"
    )
    check(os.path.exists(remediation_py), "runtime/remediation.py exists")
    if os.path.exists(remediation_py):
        rmtext = open(remediation_py).read()
        for marker in (
            "class RemediationController", "class FlagdActuator",
            "class SamplingActuator", "class TokenBucket",
            'path="remediation"', "STATE_FAILED", "rollback",
        ):
            check(marker in rmtext, f"runtime/remediation.py declares {marker}")
    rem_knobs = registries.get("REMEDIATION_KNOBS") or {}
    enable_spec = rem_knobs.get("ANOMALY_REMEDIATION_ENABLE")
    check(
        enable_spec is not None and enable_spec[1] == 0,
        "auto-mitigation defaults OFF (ANOMALY_REMEDIATION_ENABLE=0)",
    )
    flag_writers: set[str] = set()
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            fpath = os.path.join(dirpath, fname)
            try:
                tree = ast.parse(open(fpath).read())
            except SyntaxError:
                continue
            for node in ast.walk(tree):
                names = []
                if isinstance(node, ast.ImportFrom):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.Import):
                    names = [a.name.split(".")[-1] for a in node.names]
                if "atomic_write_doc" in names:
                    flag_writers.add(
                        os.path.relpath(fpath, pkg_root).replace(os.sep, "/")
                    )
    expected_flag_writers = {
        "utils/flag_ui.py",        # the flagd-ui editor surface
        "runtime/remediation.py",  # the mitigation actuator
    }
    check(
        flag_writers == expected_flag_writers,
        "remediation.py + flag_ui.py are the only flag-store writers "
        f"(atomic_write_doc importers {sorted(flag_writers)})",
    )
    mitigbench_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "mitigbench.py"
    )
    check(os.path.exists(mitigbench_py), "runtime/mitigbench.py exists")
    check(
        "mitigbench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has a mitigbench target",
    )
    check(
        "remediation:" in pyproject,
        "pyproject registers the remediation marker",
    )
    remediation_tests = os.path.join(ROOT, "tests", "test_remediation.py")
    check(
        os.path.exists(remediation_tests), "tests/test_remediation.py exists"
    )
    if os.path.exists(remediation_tests):
        rttext = open(remediation_tests).read()
        for marker in (
            "test_flapping_detector_cannot_oscillate_flags",
            "test_degraded_flagd_never_blocks_the_hot_path",
            "test_standby_observes_but_never_actuates",
            "test_fenced_daemon_actuation_refused",
            "test_rollback_on_failed_recovery",
            "test_flight_evidence_on_act_revert_rollback",
        ):
            check(marker in rttext, f"remediation suite pins {marker}")
    flag_ui_tests = os.path.join(ROOT, "tests", "test_flag_ui.py")
    if os.path.exists(flag_ui_tests):
        fut = open(flag_ui_tests).read()
        check(
            "test_torn_flag_file_write_never_corrupts_live_store" in fut,
            "flag suite pins the torn-write regression",
        )

    # 11) sharded detector fleet (runtime/fleet.py ring + membership +
    #     guardrailed reshard; runtime/aggregator.py scatter-gather):
    #     the aggregator NEVER touches detector state (the query-plane
    #     no-direct-read discipline, pinned the same grep way), the
    #     ring's placement hash is process-stable (no hash()), the
    #     Makefile has the fleetbench drill, and the fleet suite pins
    #     the property/chaos proofs.
    fleet_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "fleet.py"
    )
    check(os.path.exists(fleet_py), "runtime/fleet.py exists")
    if os.path.exists(fleet_py):
        fleet_text = open(fleet_py).read()
        for marker in (
            "class HashRing", "class FleetMembership",
            "def merge_shard_arrays", "def key_hash64",
            "def shard_key", "TokenBucket", "health_check",
        ):
            check(marker in fleet_text, f"runtime/fleet.py declares {marker}")
        check(
            "blake2b" in fleet_text,
            "fleet.py hashes ring keys with a process-stable digest",
        )
    agg_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "aggregator.py"
    )
    check(os.path.exists(agg_py), "runtime/aggregator.py exists")
    if os.path.exists(agg_py):
        agg_text = open(agg_py).read()
        for marker in (
            "class FleetAggregator", "class AggregatorService",
            "shards_answered", "shards_total",
        ):
            check(marker in agg_text, f"runtime/aggregator.py declares {marker}")
        check(
            "detector.state" not in agg_text
            and "_dispatch_lock" not in agg_text
            and "snapshot_fn" not in agg_text,
            "aggregator.py reads shards only over HTTP (no detector "
            "state / dispatch lock / snapshot helper reference)",
        )
    check(
        "fleetbench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has a fleetbench target",
    )
    check(
        "fleet:" in pyproject,
        "pyproject registers the fleet marker",
    )
    check(
        "def measure_reshard" in open(os.path.join(
            ROOT, "opentelemetry_demo_tpu", "runtime", "replbench.py"
        )).read(),
        "replbench.py grows the shard-kill -> reshard drill",
    )
    fleet_tests = os.path.join(ROOT, "tests", "test_fleet.py")
    check(os.path.exists(fleet_tests), "tests/test_fleet.py exists")
    if os.path.exists(fleet_tests):
        fttext = open(fleet_tests).read()
        for marker in (
            "test_ring_balance_within_bound",
            "test_minimal_key_movement_on_leave_and_join",
            "test_placement_deterministic_across_processes",
            "test_flapping_shard_freezes_ring_within_budget",
            "test_stalled_but_serving_shard_not_declared_dead",
            "test_blackholed_shard_degrades_to_labeled_partial",
            "test_noisy_tenant_sheds_alone",
            "test_reshard_converges_bit_exact",
        ):
            check(marker in fttext, f"fleet suite pins {marker}")

    # 12) two-pass native scanner (native/ingest.cc + runtime/native.py,
    #     the r15 decode-wall rework): the raw C entry points are
    #     called ONLY from runtime/native.py (monopoly pin, same
    #     pattern as frame.py's byte-primitive fence — a second caller
    #     would fork the ctypes contract and the GIL-release story),
    #     native and the Python fallback share ONE verdict vocabulary
    #     (malformed → ValueError/-1 → the receivers' 400; no new bare
    #     error path), and the decodebench/fuzz surfaces exist.
    native_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "native.py"
    )
    check(os.path.exists(native_py), "runtime/native.py exists")
    otd_entry_callers: set[str] = set()
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            fpath = os.path.join(dirpath, fname)
            text = open(fpath, errors="replace").read()
            # The C ABI surface: any otd_decode/otd_scan/otd_extract
            # reference outside native.py is a second ctypes caller.
            if any(
                marker in text
                for marker in (
                    "otd_decode_otlp", "otd_decode_otlp_many",
                    "otd_scan_otlp", "otd_extract_otlp",
                    "otd_decode_orders",
                )
            ):
                otd_entry_callers.add(
                    os.path.relpath(fpath, pkg_root).replace(os.sep, "/")
                )
    check(
        otd_entry_callers == {"runtime/native.py"},
        "native decode entry points are called only from native.py "
        f"(callers {sorted(otd_entry_callers)})",
    )
    ntext = open(native_py).read()
    for marker in (
        "def scan_otlp", "def extract_otlp", "def decode_otlp_many",
        "SHARD_MIN_BYTES_DEFAULT", "malformed OTLP payload",
    ):
        check(marker in ntext, f"runtime/native.py declares {marker!r}")
    ingest_cc = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "native", "ingest.cc"
    )
    cctext = open(ingest_cc).read()
    for marker in ("scan_request", "extract_span", "otd_scan_otlp",
                   "otd_extract_otlp", "payload_rows"):
        check(marker in cctext, f"native/ingest.cc declares {marker}")
    # One verdict vocabulary: the pool maps BOTH engines' per-payload
    # verdicts into the same errors dict the receivers answer 400
    # from; native.py raises ValueError for whole-batch failures
    # exactly like otlp.decode_export_request's WireError(ValueError).
    ptext = open(pool_py).read()
    check(
        'ValueError("malformed OTLP payload")' in ptext,
        "ingest pool maps native per-payload verdicts to the "
        "fallback's ValueError vocabulary",
    )
    check(
        "decodebench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has a decodebench target",
    )
    ni_tests = os.path.join(ROOT, "tests", "test_native_ingest.py")
    if os.path.exists(ni_tests):
        nitext = open(ni_tests).read()
        for marker in (
            "test_native_and_python_verdicts_agree_on_every_seed",
            "test_shard_split_varints_bit_exact",
            "test_truncation_at_every_pass1_boundary",
            "test_max_nesting_submessages",
        ):
            check(marker in nitext, f"scanner fuzz suite pins {marker}")

    # 13) elastic fleet (runtime/autoscale.py + the adoption tier in
    #     fleet.py/daemon.py): the autoscaler defaults OFF (the same
    #     hard opt-in as remediation — a ring that resizes itself is a
    #     product decision, not a knob drift), every decision passes
    #     the SIXTH fenced epoch path (path="autoscale"), dead-peer
    #     keyspace adoption is automatic in-daemon (ring_heir + adopt
    #     + merge under the dispatch lock), the k8s generator emits
    #     the collector-side fleet routing from the REAL ring, and the
    #     chaos suite pins the proofs.
    autoscale_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "autoscale.py"
    )
    check(os.path.exists(autoscale_py), "runtime/autoscale.py exists")
    if os.path.exists(autoscale_py):
        astext = open(autoscale_py).read()
        for marker in (
            "class AutoscaleController", "TokenBucket",
            'path="autoscale"', "observe_only", "budget_exhausted",
            "refused_apply",
        ):
            check(marker in astext, f"runtime/autoscale.py declares {marker!r}")
    as_knobs = registries.get("AUTOSCALE_KNOBS") or {}
    as_enable = as_knobs.get("ANOMALY_AUTOSCALE_ENABLE")
    check(
        as_enable is not None and as_enable[1] == 0,
        "autoscaling defaults OFF (ANOMALY_AUTOSCALE_ENABLE=0)",
    )
    if os.path.exists(fleet_py):
        fleet_text = open(fleet_py).read()
        for marker in ("def adopt", "def ring_heir", "adoptive"):
            check(
                marker in fleet_text,
                f"runtime/fleet.py grows the adoption tier ({marker})",
            )
    for marker in (
        "_adopt_shard", "_retarget_adoption_mirror",
        "AutoscaleController",
    ):
        check(
            marker in daemon_text,
            f"daemon wires automatic adoption + autoscaler ({marker})",
        )
    check(
        "fleet_routing_configmap" in k8s_text,
        "k8s generator emits the ring-derived fleet routing configmap",
    )
    check(
        "def measure_adoption" in open(os.path.join(
            ROOT, "opentelemetry_demo_tpu", "runtime", "replbench.py"
        )).read(),
        "replbench.py grows the autoscale + SIGKILL-adoption drill",
    )
    check(
        "autoscalebench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has an autoscalebench target",
    )
    if os.path.exists(fleet_tests):
        fttext = open(fleet_tests).read()
        for marker in (
            "test_dead_peer_frame_adopted_automatically",
            "test_stalled_but_serving_shard_never_auto_adopted",
            "test_budget_exhausted_freezes_adoption",
            "test_observe_only_default_never_proposes",
            "test_fenced_decision_refused",
            "test_autoscale_sigkill_adoption_live",
        ):
            check(marker in fttext, f"elastic-fleet suite pins {marker}")

    # 14) counterfactual control (runtime/shadow.py + the preflight
    #     interlude in remediation.py): the pre-flight verifier
    #     defaults OFF (same hard opt-in as remediation/autoscale — a
    #     gate that can refuse mitigations is a product decision), the
    #     shadow replay is built by the SAME pipeline builder
    #     replaybench uses (bit-identity by construction), it touches
    #     live state through the disk-backed HistoryReader ONLY (the
    #     query.py isolation contract), and the suite + bench legs pin
    #     both verdict directions.
    shadow_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "shadow.py"
    )
    check(os.path.exists(shadow_py), "runtime/shadow.py exists")
    if os.path.exists(shadow_py):
        shtext = open(shadow_py).read()
        for marker in (
            "class ShadowVerifier", "def build_shadow_pipeline",
            "def suppress_transform", "PreflightVerdict",
            "REASON_DEADLINE", "REASON_INSUFFICIENT",
        ):
            check(marker in shtext, f"runtime/shadow.py declares {marker!r}")
        check(
            "detector.state" not in shtext
            and "_dispatch_lock" not in shtext,
            "shadow.py replays from the disk-backed reader only "
            "(no detector.state / _dispatch_lock reference)",
        )
    sh_knobs = registries.get("SHADOW_KNOBS") or {}
    sh_enable = sh_knobs.get("ANOMALY_SHADOW_ENABLE")
    check(
        sh_enable is not None and sh_enable[1] == 0,
        "pre-flight verification defaults OFF (ANOMALY_SHADOW_ENABLE=0)",
    )
    rem_text = open(os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "remediation.py"
    )).read()
    for marker in (
        "STATE_PREFLIGHT", "_finish_preflight", "class CollectorActuator",
    ):
        check(
            marker in rem_text,
            f"remediation.py grows the preflight interlude ({marker})",
        )
    check(
        "build_shadow_pipeline" in open(os.path.join(
            ROOT, "opentelemetry_demo_tpu", "runtime", "replaybench.py"
        )).read(),
        "replaybench builds its replay pipeline through the ONE "
        "shared builder (shadow.build_shadow_pipeline)",
    )
    check(
        "def measure_shadow" in open(os.path.join(
            ROOT, "opentelemetry_demo_tpu", "runtime", "mitigbench.py"
        )).read(),
        "mitigbench.py grows the shadow pre-flight leg",
    )
    check(
        "shadowbench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has a shadowbench target",
    )
    check(
        "shadow:" in open(os.path.join(ROOT, "pyproject.toml")).read(),
        "pyproject registers the shadow marker",
    )
    shadow_tests = os.path.join(ROOT, "tests", "test_shadow.py")
    check(os.path.exists(shadow_tests), "tests/test_shadow.py exists")
    if os.path.exists(shadow_tests):
        sttext = open(shadow_tests).read()
        for marker in (
            "test_bit_identity_with_replaybench",
            "test_would_help_mitigation_released",
            "test_wrong_mitigation_refused",
            "test_deadline_miss_refuses",
            "test_refused_verdict_refunds_and_stays_pending",
            "test_fenced_daemon_never_preflights",
            "test_isolation_pin_no_live_state",
            "test_exact_revert_prior_restored",
            "test_refcounted_shared_holds",
        ):
            check(marker in sttext, f"shadow suite pins {marker}")

    # §15 native front door (r19): the zero-Python OTLP/HTTP door —
    # the native acceptor exists with its framing verdicts, the Python
    # control plane exists WITHOUT any Python HTTP machinery (the
    # per-payload loop is native by construction, and this pin keeps
    # it that way), the knob registry stays strictly opt-in, and the
    # parity/fuzz suite + bench legs are pinned by name.
    fd_cc = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "native", "frontdoor.cc"
    )
    check(os.path.exists(fd_cc), "native/frontdoor.cc exists")
    if os.path.exists(fd_cc):
        fdcc = open(fd_cc).read()
        for marker in (
            "otd_fd_start", "otd_fd_next", "otd_fd_respond",
            "otd_fd_quiesce", "otd_fd_stop", "Content-Length",
        ):
            check(marker in fdcc, f"native/frontdoor.cc declares {marker}")
    fd_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "frontdoor.py"
    )
    check(os.path.exists(fd_py), "runtime/frontdoor.py exists")
    if os.path.exists(fd_py):
        fdtext = open(fd_py).read()
        for marker in (
            "class FrontDoorServer", "frontdoor_next", "frontdoor_body",
            "IngestPoolSaturated",
        ):
            check(marker in fdtext, f"runtime/frontdoor.py declares {marker!r}")
        # AST, not substring: the module's docstring is ALLOWED to
        # name the machinery it bans; only a real import trips this.
        fd_imports: set[str] = set()
        for node in ast.walk(ast.parse(fdtext)):
            if isinstance(node, ast.Import):
                fd_imports.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.module:
                fd_imports.add(node.module)
        fd_banned = {"http", "socketserver", "urllib", "wsgiref"}
        check(
            not any(
                m.split(".", 1)[0] in fd_banned for m in fd_imports
            ),
            "frontdoor.py imports no Python HTTP machinery (the "
            "zero-Python per-payload pin: bodies go socket→native "
            "buffer→decode ticket, never through a Python request "
            "object)",
        )
    check(
        "frontdoor" in open(os.path.join(
            ROOT, "opentelemetry_demo_tpu", "runtime", "native.py"
        )).read(),
        "runtime/native.py binds the front-door surface",
    )
    fd_knobs = registries.get("FRONTDOOR_KNOBS") or {}
    fd_enable = fd_knobs.get("ANOMALY_FRONTDOOR_ENABLE")
    check(
        fd_enable is not None and fd_enable[1] == 0,
        "front door defaults OFF (ANOMALY_FRONTDOOR_ENABLE=0 — the "
        "Python receiver stays the default path)",
    )
    check(
        "frontdoorbench:" in open(os.path.join(ROOT, "Makefile")).read(),
        "Makefile has a frontdoorbench target",
    )
    check(
        "BENCH_FRONTDOOR" in open(os.path.join(ROOT, "bench.py")).read(),
        "bench.py grows the BENCH_FRONTDOOR leg",
    )
    check(
        "frontdoor:" in open(os.path.join(ROOT, "pyproject.toml")).read(),
        "pyproject registers the frontdoor marker",
    )
    fdb_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "frontdoorbench.py"
    )
    check(os.path.exists(fdb_py), "runtime/frontdoorbench.py exists")
    if os.path.exists(fdb_py):
        fdbtext = open(fdb_py).read()
        for marker in (
            "def measure_frontdoor_vs_pool",
            "def measure_million_key_soak",
            "rss_per_million_keys_mb",
        ):
            check(
                marker in fdbtext,
                f"frontdoorbench.py declares {marker!r}",
            )
    fd_tests = os.path.join(ROOT, "tests", "test_frontdoor.py")
    check(os.path.exists(fd_tests), "tests/test_frontdoor.py exists")
    if os.path.exists(fd_tests):
        fttext = open(fd_tests).read()
        for marker in (
            "test_frontdoor_status_parity_shared_corpus",
            "test_frontdoor_columns_byte_identical",
            "test_frontdoor_truncation_every_boundary",
            "test_frontdoor_slowloris",
            "test_frontdoor_pipelined_requests",
            "test_frontdoor_oversized_413",
            "test_frontdoor_chunked_rejected",
            "test_frontdoor_faultwire_chaos",
            "test_frontdoor_saturation_retry_after",
            "test_frontdoor_graceful_drain",
            "test_frontdoor_no_python_http_in_payload_path",
            "test_intern_100k_one_flush_bit_identity",
            "test_intern_known_batch_lock_free",
            "test_fleet_drift_refusal_large_tables",
        ):
            check(marker in fttext, f"front-door suite pins {marker}")

    # §16 key lifecycle plane (r20): the bounded interner, the idle
    # evictor, the degradation ladder, and the generation fence. The
    # knob registry is consumer-threaded by the loop above; here we
    # pin the semantics the knobs promise (two-edge hysteresis needs
    # high > low; a 0-key evict batch would make the ladder's evict
    # rung a no-op), the one concurrency invariant everything rests
    # on (interner retirement happens inside the pipeline dispatch
    # lock — an evictor that retires outside it races the pump's
    # intern path), and the suite names.
    ks_knobs = registries.get("KEYSPACE_KNOBS") or {}
    ks_enable = ks_knobs.get("ANOMALY_KEYSPACE_ENABLE")
    check(
        ks_enable is not None and ks_enable[1] == 1,
        "keyspace plane defaults ON (ANOMALY_KEYSPACE_ENABLE=1 — "
        "bounded memory is the default posture, not an opt-in)",
    )
    ks_hi = ks_knobs.get("ANOMALY_KEYSPACE_HIGH_WATERMARK")
    ks_lo = ks_knobs.get("ANOMALY_KEYSPACE_LOW_WATERMARK")
    check(
        ks_hi is not None and ks_lo is not None and ks_lo[1] < ks_hi[1] <= 1.0,
        "keyspace watermarks form a hysteresis band "
        "(LOW < HIGH <= 1.0 — equal edges would flap the ladder)",
    )
    ks_batch = ks_knobs.get("ANOMALY_KEYSPACE_EVICT_BATCH")
    check(
        ks_batch is not None and ks_batch[1] >= 1,
        "keyspace evict batch >= 1 (a 0 batch silently disables the "
        "evict rung)",
    )
    check(
        "ANOMALY_QUERY_EVICTED_LOOKBACK_S"
        in (registries.get("QUERY_KNOBS") or {}),
        "QUERY_KNOBS carries ANOMALY_QUERY_EVICTED_LOOKBACK_S "
        "(evicted-key answers need a bounded history search window)",
    )
    ks_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "keyspace.py"
    )
    check(os.path.exists(ks_py), "runtime/keyspace.py exists")
    if os.path.exists(ks_py):
        kstext = open(ks_py).read()
        for marker in (
            "class KeyspaceManager", "def evict_idle", "def tick",
            "def process_rss_bytes",
        ):
            check(marker in kstext, f"runtime/keyspace.py declares {marker!r}")
        # AST, not substring: every retire_services(...) call in the
        # evictor must sit under a `with ... _dispatch_lock:` block.
        # (scripts/staticcheck's eviction-lock pass enforces this
        # repo-wide; this pin keeps the module itself honest even if
        # the pass is ever skipped.)
        unlocked = []
        tree = ast.parse(kstext)

        def _locked(node: ast.AST, guarded: bool) -> None:
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "retire_services"
                and not guarded
            ):
                unlocked.append(node.lineno)
            inside = guarded
            if isinstance(node, ast.With):
                for item in node.items:
                    src = ast.unparse(item.context_expr)
                    if "_dispatch_lock" in src:
                        inside = True
            for child in ast.iter_child_nodes(node):
                _locked(child, inside)

        _locked(tree, False)
        check(
            not unlocked,
            "keyspace.py retires interner ids only under the dispatch "
            f"lock (unguarded retire_services at lines {unlocked or '—'})",
        )
    pl_py = os.path.join(
        ROOT, "opentelemetry_demo_tpu", "runtime", "pipeline.py"
    )
    pltext = open(pl_py).read()
    for marker in (
        "KEYSPACE_LEVEL_EVICT", "KEYSPACE_LEVEL_THROTTLE",
        "KEYSPACE_LEVEL_COLLAPSE", "KEYSPACE_LEVEL_SHED",
        "def keyspace_update", "def keyspace_newkey_gate",
        "def admission_retry_after",
    ):
        check(marker in pltext, f"runtime/pipeline.py declares {marker!r}")
    check(
        "keyspace:" in open(os.path.join(ROOT, "pyproject.toml")).read(),
        "pyproject registers the keyspace marker",
    )
    check(
        "measure_churn_soak"
        in open(os.path.join(
            ROOT, "opentelemetry_demo_tpu", "runtime", "frontdoorbench.py"
        )).read(),
        "frontdoorbench.py grows the churn-soak gate",
    )
    check(
        "churn_ok" in open(os.path.join(ROOT, "bench.py")).read(),
        "bench.py lifts the churn_ok verdict",
    )
    ks_tests = os.path.join(ROOT, "tests", "test_keyspace.py")
    check(os.path.exists(ks_tests), "tests/test_keyspace.py exists")
    if os.path.exists(ks_tests):
        kttext = open(ks_tests).read()
        for marker in (
            "test_saturated_intern_many_dense_and_bit_stable",
            "test_all_overflow_flush_roundtrips_the_frame_format",
            "test_retire_recycles_ids_behind_a_generation_bump",
            "test_two_edge_hysteresis_one_rung_per_hold",
            "test_throttle_rung_isolates_tenants",
            "test_shed_rung_answers_429_through_the_python_door",
            "test_evict_folds_zeroes_and_retires_idle_keys",
            "test_fleet_merge_refuses_generation_drift",
            "test_replication_delta_refused_across_generations",
            "test_checkpoint_roundtrips_generation_and_tombstones",
            "test_evicted_key_answers_from_history",
            "test_overflow_bucket_answers_are_labeled",
        ):
            check(marker in kttext, f"keyspace suite pins {marker}")

    # no imports from the read-only reference tree
    bad = []
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames if d not in (".git", "__pycache__", "build")]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            if os.path.samefile(path, __file__):
                continue  # this checker necessarily names the pattern
            text = open(path, errors="replace").read()
            if "/root/reference" in text:
                # Citations in docstrings/comments are expected; an
                # import or open() against the tree is not.
                for line in text.splitlines():
                    s = line.strip()
                    if s.startswith(("#", '"', "'")) or "reference" not in s:
                        continue
                    if ("import" in s or "open(" in s) and "/root/reference" in s:
                        bad.append(os.path.join(dirpath, fname))
    check(not bad, f"no code imports/reads /root/reference {bad or ''}")

    print(("\nSANITY OK" if not FAILS else f"\n{len(FAILS)} FAILURES"))
    return 1 if FAILS else 0


if __name__ == "__main__":
    sys.exit(main())
