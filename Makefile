# Build/run surface — the analogue of the reference's Makefile
# (/root/reference/Makefile:100-285: start/stop, run-tests,
# run-tracetesting, generate-protobuf, check). JAX on CPU is forced for
# local targets; bench runs on whatever accelerator jax.devices() finds.

PY      := python
CPU_ENV := env JAX_PLATFORMS=cpu

.PHONY: start start-minimal start-kafka start-load test tracetest kafka-interop bench overloadbench ingestbench decodebench spinebench frontdoorbench replbench fleetbench autoscalebench replaybench mitigbench shadowbench querybench explainbench gen-k8s gen-proto gen-dashboards build-native staticcheck check clean

start:          ## serve the shop stack (gateway :8080 + detector + 5 users)
	$(CPU_ENV) $(PY) scripts/serve_shop.py --users 5

start-minimal:  ## reduced profile (reference make start-minimal): no async tier, no flag-editor UI
	$(CPU_ENV) $(PY) scripts/serve_shop.py --users 5 --minimal

start-kafka:    ## shop with the async tier over a REAL broker socket
	$(CPU_ENV) $(PY) scripts/serve_shop.py --users 5 --kafka auto

start-load:     ## drive a remote gateway (TARGET=http://host:8080)
	$(CPU_ENV) $(PY) scripts/serve_shop.py --load-only --target $(or $(TARGET),http://127.0.0.1:8080) --users 5

test:           ## unit + integration suite (CPU mesh)
	$(CPU_ENV) $(PY) -m pytest tests/ -x -q

tracetest:      ## trace-based suites over a live gateway (SURVEY.md §4)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.tracetest tracetesting

kafka-interop:  ## wire-client suite vs a real broker (KAFKA_ADDR=host:9092; unset = in-repo broker)
	$(CPU_ENV) $(PY) -m pytest tests/test_kafka_interop.py -v

bench:          ## flagship benchmark (ONE json line; fails without a TPU)
	$(PY) bench.py

overloadbench:  ## overload saturation driver (ONE json line: bounded queue, zero error-lane shed, brownout, recovery)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.overloadbench

ingestbench:    ## host-ingest engines + decode-pool worker sweep (same methodology as bench.py's host_ingest_*)
	$(CPU_ENV) $(PY) scripts/bench_ingest.py --workers 1,2,4

decodebench:    ## raw two-pass scanner microbench: pass-1 scan vs pass-2 extract per thread + one-fat-payload shard scaling
	$(CPU_ENV) $(PY) scripts/bench_ingest.py --raw

spinebench:     ## end-to-end ingest spine: payload → flagged report, workers × ring-depth sweep (ONE json line)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.spinebench

frontdoorbench: ## native front door vs in-process pool at matched workers + ≥1M-distinct-key cardinality soak (ONE json line)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.frontdoorbench

replbench:      ## hot-standby failover drill (ONE json line: replication lag p99, failover TTD, exact convergence)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.replbench

fleetbench:     ## sharded-fleet reshard drill (ONE json line: SIGKILL a shard under live Kafka+OTLP load, reshard TTD, witness-pinned bit-exact answers, blackholed-shard partial answers, noisy-tenant isolation; folds in the autoscalebench leg)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.replbench --fleet

autoscalebench: ## elastic-fleet live drill alone (ONE json line: ramp to saturation, autoscaler proposes scale-out, SIGKILL a shard mid-resize, automatic adoption TTA, bit-exact witness pin, no oscillation)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.replbench --autoscale

replaybench:    ## history time-travel drill (ONE json line: record an incident, replay the segment log at N× wall clock, pin bit-identical verdicts, range-query p99)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.replaybench

mitigbench:     ## closed-loop auto-mitigation drill (ONE json line: time-to-mitigate per flagd scenario, rollback drill, no-oscillation gate; BENCH_SHADOW=1 folds in the shadow leg)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.mitigbench

shadowbench:    ## counterfactual pre-flight drill alone (ONE json line: shadow-replay bit-identity at ≥10× wall, would-help released vs wrong-flag refused with zero actuator writes, collector keep/drop ratio + exact revert)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.mitigbench --shadow

querybench:     ## live query plane under concurrent ingest (ONE json line: query p99/qps, ingest interference ratio)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.querybench

explainbench:   ## verdict-provenance canary (ONE json line: provenance-on/off ABAB overhead ratio gated ≤1.03, /query/explain p99 under the live-ingest hammer)
	$(CPU_ENV) $(PY) -m opentelemetry_demo_tpu.runtime.spinebench --explain

gen-k8s:        ## regenerate deploy/k8s manifests
	$(PY) -m opentelemetry_demo_tpu.utils.k8s --out deploy/k8s

build-native:   ## C++ ingest + currency kernels
	$(PY) -m opentelemetry_demo_tpu.runtime.native

staticcheck:    ## AST invariant analysis (scripts/staticcheck; no jax, <10s)
	$(PY) -m scripts.staticcheck

check:          ## fast static sanity (no network, no device)
	$(PY) -m compileall -q opentelemetry_demo_tpu tests scripts bench.py __graft_entry__.py
	$(PY) -m scripts.staticcheck
	SANITYCHECK_SKIP_STATICCHECK=1 $(PY) scripts/sanitycheck.py

gen-proto:      ## regenerate protobuf stubs (build artifact)
	bash scripts/gen_proto.sh

gen-dashboards: ## regenerate deploy/grafana/*.json from telemetry.dashboards
	$(PY) -c "from opentelemetry_demo_tpu.telemetry.dashboards import write_grafana_dashboards as w; print('\n'.join(w('deploy/grafana')))"

clean:
	$(PY) -m opentelemetry_demo_tpu.runtime.native clean 2>/dev/null || true
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
