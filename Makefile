# Top-level entry points (SURVEY.md §7.2 step 6: one-command test/CI).
#
#   make test    — full verification: Python suite (virtual 8-device CPU
#                  mesh via tests/conftest.py) + native builds + shim
#                  selftest + MPI-backend typecheck
#   make native  — build both sort binaries (local backend) + bench tools
#   make chip-test — ON-CHIP regression gate (needs a real TPU): real-
#                  Mosaic bitonic vs lax.sort numerics + timing at 2^26,
#                  segment_pack, the 5-pattern adversarial battery; one
#                  JSONL row appended to bench/BASELINE_RESULTS.jsonl.
#                  Finishes in minutes — run it in every chip session.
#   make telemetry-selftest — end-to-end check of the unified telemetry
#                  layer: a tiny TPU-path sort with SORT_TRACE (span
#                  JSONL) + a native run with COMM_STATS, both validated
#                  by `python -m mpitest_tpu.report --check`
#   make fault-selftest — chaos-test matrix (ISSUE 3): the full
#                  SORT_FAULTS grid (9 fault sites x {sample, radix}),
#                  persistent-fault ladder cells, the CLI's typed exit
#                  codes, and the native COMM_FAULTS kill/stall drills.
#                  Every cell must recover with a fingerprint-verified
#                  result or fail loudly with a nonzero exit — zero
#                  silent-wrong-answer cells; warm verifier overhead is
#                  asserted < 5% of sort wall.
#   make ingest-selftest — end-to-end check of the streaming ingest
#                  pipeline WITH the native encode engine forced on
#                  (ISSUE 6): a SORTBIN1 sort forced through the chunked
#                  pipeline under SORT_TRACE; `report.py --check
#                  --require-ingest-overlap` then asserts the emitted
#                  ingest.* spans show parse/encode genuinely
#                  overlapping the host→device transfers AND the
#                  recorded ingest_ratio meets the 0.5x end-to-end
#                  gate; bench/ingest_selftest.py additionally asserts
#                  native encode >= 2x the Python engine on this host
#   make native-encode — build native/libencode.so (the C ingest
#                  engine behind SORT_NATIVE_ENCODE, ISSUE 6)
#   make multichip-selftest — the scale-out gate (ISSUE 7), on a
#                  virtual 8-device CPU mesh so it runs on any image:
#                  8-device output bit-identical to 1-device for both
#                  algorithms (uniform / N<P / non-divisible / skewed),
#                  per-rank exchange-byte imbalance under the gate, and
#                  negotiated capacity strictly below the worst-case
#                  cap with zero overflow retries on skewed inputs
#   make serve-selftest — the sort-as-a-service gate (ISSUE 8): spins
#                  drivers/sort_server.py in subprocesses and drives
#                  bench/serve_load.py's closed-loop small-request mix
#                  against them.  Asserts: warm-cache requests record
#                  ZERO compile spans (the AOT executor cache), batched
#                  multi-tenant dispatch is bit-identical to
#                  per-request sorts AND >= 2x their dispatch
#                  throughput, backpressure rejections and injected
#                  per-request faults come back as TYPED errors while
#                  the server keeps serving, and SIGTERM drains
#                  gracefully.  The server span stream then passes
#                  `report.py --check --require-registered-spans` and
#                  renders the p50/p99 SLO table.
#   make chaos-serve-selftest — the wire-chaos gate (ISSUE 11): a real
#                  sort_server behind the chaos TCP proxy
#                  (bench/wire_chaos.py).  Every wire-fault cell (torn
#                  header, stalled/slow-dripped payload, raw-RST kill
#                  mid-payload, mid-response disconnect, connect-then-
#                  silence) must end with the server alive, in-flight
#                  admission bytes back to 0 (scraped from /metrics),
#                  zero leaked handler threads, and a clean follow-up
#                  request served bit-exact; a wedged dispatch must
#                  trip the watchdog (healthz 503, typed fast
#                  rejections, flight-recorder artifact that passes
#                  report.py --check) and recover via the breaker's
#                  half-open probe; and hedging must cut the
#                  injected-tail p99 strictly below the unhedged run.
#   make external-selftest — the out-of-core gate (ISSUE 15): a
#                  dataset 4x a forced SORT_MEM_BUDGET spills to
#                  SORTBIN1-framed sorted runs and k-way merges back
#                  bit-identical to the in-memory sort; key+payload
#                  record parity vs the numpy stable argsort-gather
#                  oracle across all dtypes; spill_corrupt/merge_drop
#                  fault cells recover verified or fail typed; a
#                  spawned server serves a payload_bytes request and
#                  an over-admission request (via the spill tier) each
#                  bit-identical to the solo in-memory oracle.
#   make spillperf-selftest — the disk-speed gate (ISSUE 20): on a
#                  simulated slow disk (SORT_SPILL_THROTTLE_MBPS token
#                  bucket) an external sort over compressed SORTRUN2
#                  runs must run >= 1.5x the raw-run baseline (both
#                  legs bit-identical to np.sort AND the in-memory
#                  sort), and the final merge's measured read-ahead/
#                  write-behind disk/compute overlap must be >= 0.5.
#   make durability-selftest — the crash-durability gate (ISSUE 18):
#                  a real spawned server is SIGKILLed mid-external-sort
#                  (merge wedged by an armed stall, every spill run
#                  already committed to the dataset's journaled .mfst
#                  manifest); a restarted server retrying the same
#                  dataset_id must resume at the merge phase — reply
#                  bit-identical, plan digest resumed:true, ZERO
#                  external.run spans in the restart's trace, and the
#                  manifest retired afterwards.
#   make localsort-selftest — the fused local-engine gate (ISSUE 17):
#                  interpret-mode bit-identity vs the lax engine across
#                  every codec dtype x input class (kernel + api level,
#                  ladder pinned off), one pallas_call per planned
#                  radix pass, narrow key-width plans shorter than
#                  full width, external-sort merge device-vs-host
#                  bit-identical, and the radix_compact policy's pass
#                  prediction honest (lying profiles stamp regret).
#   make lint    — static analysis (ISSUE 4): sortlint (the project's
#                  custom AST rules — env-knob registry, span schema,
#                  SPMD safety, fault coverage, typed core), threadlint
#                  (ISSUE 19: interprocedural concurrency analysis over
#                  the registered thread roots and lock ranks), the
#                  cross-backend comm parity checker, a
#                  -Wconversion/-Wshadow -Werror pass over every C
#                  source, and mypy strict on the typed core / a
#                  clang-tidy pass where those tools are installed
#                  (CI's lint job installs mypy; this image ships
#                  neither).  No JAX device needed.
#   make sanitize-selftest — the native sanitizer matrix: TSan on the
#                  pthreads backend (comm_selftest + seeded comm_fuzz —
#                  a real race detector over the barrier/alltoallv
#                  paths), ASan+UBSan on BOTH backends (pthreads and
#                  the fork-based minimpi runtime), with a
#                  cross-sanitizer checksum differential and an
#                  empty-by-policy suppressions file
#                  (tools/sanitize.supp).
#   make knob-docs — regenerate README's env-knob reference table from
#                  the central registry (mpitest_tpu/utils/knobs.py)
#   make clean   — remove all build artifacts

PYTHON ?= python3

.PHONY: test native native-encode chip-test telemetry-selftest \
    ingest-selftest fault-selftest multichip-selftest serve-selftest \
    chaos-serve-selftest planner-selftest external-selftest \
    spillperf-selftest durability-selftest doctor-selftest \
    localsort-selftest lint \
    threadlint-fixtures cwarn-check typecheck tidy-check knob-docs \
    sanitize-selftest bench-history clean

chip-test:
	$(PYTHON) -u bench/chip_regression.py

# The CI test job: the pytest suite on the virtual CPU mesh.
test: native
	$(PYTHON) -m pytest tests/ -q

native:
	$(MAKE) -C mpi_sample_sort BACKEND=local
	$(MAKE) -C mpi_radix_sort BACKEND=local
	$(MAKE) -C bench BACKEND=local
	$(MAKE) -C bench mpi-syntax-check

# The native ingest engine alone (ISSUE 6): native/libencode.so for the
# ctypes shim (utils/native_encode.py; SORT_NATIVE_ENCODE selects it).
native-encode:
	$(MAKE) -C bench libencode

# One-command proof that both telemetry producers emit what the report
# CLI can validate: TPU span stream (SORT_TRACE) on a virtual CPU mesh
# + native COMM_STATS from a pthreads sort, same tiny input.  The LIVE
# leg (ISSUE 10) then spins a real sort_server and proves the
# operational layer: client trace ids echoed and reconstructable via
# `report.py --trace-id` (queue wait, batch membership, dispatch,
# reply), /metrics exposition valid with every exported name registered
# and request counts reconciling exactly with the client, /healthz +
# /varz + /flightrecorder + /profile live, a fault-injected typed error
# leaving a flight-recorder artifact that `report.py --check` accepts,
# and a SORT_TRACE_SAMPLE-downsampled stream still schema-valid.
TELEMETRY_TMP := /tmp/mpitest_telemetry_selftest
telemetry-selftest:
	$(MAKE) -C mpi_radix_sort BACKEND=local
	rm -rf $(TELEMETRY_TMP) && mkdir -p $(TELEMETRY_TMP)
	$(PYTHON) -c "import numpy as np; np.savetxt('$(TELEMETRY_TMP)/keys.txt', \
	    np.random.default_rng(0).integers(-2**31, 2**31-1, size=4096, \
	    dtype=np.int32), fmt='%d')"
	JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	    SORT_ALGO=radix SORT_RANKS=4 \
	    SORT_TRACE=$(TELEMETRY_TMP)/trace.jsonl \
	    $(PYTHON) drivers/sort_cli.py $(TELEMETRY_TMP)/keys.txt
	COMM_RANKS=4 COMM_STATS=$(TELEMETRY_TMP)/comm_stats.jsonl \
	    mpi_radix_sort/radix_sort $(TELEMETRY_TMP)/keys.txt
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(TELEMETRY_TMP)/trace.jsonl $(TELEMETRY_TMP)/comm_stats.jsonl
	$(PYTHON) -m mpitest_tpu.report \
	    $(TELEMETRY_TMP)/trace.jsonl $(TELEMETRY_TMP)/comm_stats.jsonl
	# explain leg (ISSUE 12): the CLI run's decision record renders as
	# an EXPLAIN tree from the same stream; the live selftest then
	# asserts the serve-side half (plan spans registered, regret
	# metrics scraped, negotiate-off > negotiated cap regret)
	$(PYTHON) -m mpitest_tpu.report --explain $(TELEMETRY_TMP)/trace.jsonl
	# doctor leg (ISSUE 16): the same CLI trace renders through the
	# pathology diagnoser; diagnosis is a report, not a gate, so a
	# healthy run exits 0 with zero findings
	$(PYTHON) -m mpitest_tpu.report --doctor $(TELEMETRY_TMP)/trace.jsonl
	JAX_PLATFORMS=cpu \
	    $(PYTHON) -u bench/telemetry_live_selftest.py \
	    --out $(TELEMETRY_TMP)/live
	$(PYTHON) -m mpitest_tpu.report --prom $(TELEMETRY_TMP)/live/scrape.prom

# The BENCH_r01..rNN trajectory (throughput / ingest ratio / cap saving
# / serve SLO) as one markdown table with per-metric regression flags —
# the pinned snapshots nothing read across runs before ISSUE 10.
bench-history:
	$(PYTHON) tools/bench_history.py

# The chaos matrix (ISSUE 3 acceptance gate) — see bench/fault_selftest.py.
# Builds the native binaries the COMM_FAULTS drills target first.
fault-selftest:
	$(MAKE) -C mpi_radix_sort BACKEND=local
	$(MAKE) -C bench radix_sort_minimpi
	JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -u bench/fault_selftest.py

# The scale-out gate (ISSUE 7) — see bench/multichip_selftest.py.
# Virtual 8-device CPU mesh: runs on any image; identical shard_map
# code drives real chips.
multichip-selftest:
	JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -u bench/multichip_selftest.py

# The sort-as-a-service gate (ISSUE 8) — see bench/serve_load.py.
# Servers are spawned as subprocesses on a plain 1-device CPU backend
# (the fault leg forces its own 2-device virtual mesh); the final
# report passes validate the server's span stream against the
# registered schema and render the p50/p99 SLO table from it.
SERVE_TMP := /tmp/mpitest_serve_selftest
serve-selftest:
	rm -rf $(SERVE_TMP) && mkdir -p $(SERVE_TMP)
	JAX_PLATFORMS=cpu \
	    SORT_METRICS=$(SERVE_TMP)/metrics.jsonl \
	    $(PYTHON) -u bench/serve_load.py --selftest --out $(SERVE_TMP)
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(SERVE_TMP)/server_trace_batched.jsonl
	$(PYTHON) -m mpitest_tpu.report \
	    $(SERVE_TMP)/server_trace_batched.jsonl $(SERVE_TMP)/metrics.jsonl

# The self-tuning planner gate (ISSUE 14) — see
# bench/planner_selftest.py.  The adversarial mix (sorted/near-sorted/
# dup/skew/uniform, cpu:8 virtual mesh) planner-off vs planner-on:
# throughput >= 1.3x, aggregate plan_regret strictly lower, planner-off
# AND shadow byte-identical; plus the serve window-auto A/B against a
# mis-set fixed window.  The final report pass renders the explain
# trees (planner policy census included) from the recorded metrics.
PLANNER_TMP := /tmp/mpitest_planner_selftest
planner-selftest:
	rm -rf $(PLANNER_TMP) && mkdir -p $(PLANNER_TMP)
	JAX_PLATFORMS=cpu \
	    SORT_METRICS=$(PLANNER_TMP)/metrics.jsonl \
	    SORT_TRACE=$(PLANNER_TMP)/trace.jsonl \
	    $(PYTHON) -u bench/planner_selftest.py --out $(PLANNER_TMP)
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(PLANNER_TMP)/trace.jsonl
	$(PYTHON) -m mpitest_tpu.report --explain $(PLANNER_TMP)/trace.jsonl

# The out-of-core gate (ISSUE 15) — see bench/external_selftest.py.
# A dataset 4x the forced SORT_MEM_BUDGET spills to sorted runs and
# k-way merges back bit-identical to the in-memory sort; record
# (key+payload) parity vs the numpy stable argsort-gather oracle across
# all dtypes; spill_corrupt/merge_drop fault cells recover verified or
# fail typed; and a spawned server proves payload_bytes requests plus
# the over-admission spill tier end to end.  The final report pass
# validates the emitted external.* spans against the registered schema.
EXTERNAL_TMP := /tmp/mpitest_external_selftest
external-selftest:
	rm -rf $(EXTERNAL_TMP) && mkdir -p $(EXTERNAL_TMP)
	JAX_PLATFORMS=cpu \
	    SORT_TRACE=$(EXTERNAL_TMP)/trace.jsonl \
	    $(PYTHON) -u bench/external_selftest.py
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(EXTERNAL_TMP)/trace.jsonl

# The disk-speed gate (ISSUE 20) — see bench/spillperf_selftest.py.
# On a simulated slow disk (the SORT_SPILL_THROTTLE_MBPS token bucket),
# external sort over compressed SORTRUN2 runs vs the raw baseline:
# both legs bit-identical to np.sort AND the in-memory sort, the
# compressed leg >= 1.5x faster at the disk-bound budget, and the
# final merge's measured read-ahead/write-behind disk/compute overlap
# >= 0.5.  Builds the native codec first (the gate measures it; the
# pure-Python fallback is covered by the unit tests instead).
SPILLPERF_TMP := /tmp/mpitest_spillperf_selftest
spillperf-selftest:
	$(MAKE) -C bench libspillz
	rm -rf $(SPILLPERF_TMP) && mkdir -p $(SPILLPERF_TMP)
	JAX_PLATFORMS=cpu \
	    SORT_TRACE=$(SPILLPERF_TMP)/trace.jsonl \
	    $(PYTHON) -u bench/spillperf_selftest.py
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(SPILLPERF_TMP)/trace.jsonl

# The crash-durability gate (ISSUE 18) — see bench/durability_selftest.py.
# SIGKILL a real server mid-external-sort, restart, retry the same
# dataset_id: the journaled manifest must turn the crash into a
# checkpoint (resume at the merge phase, bit-identical reply, zero
# external.run spans on the restart, manifest retired).
DURABILITY_TMP := /tmp/mpitest_durability_selftest
durability-selftest:
	rm -rf $(DURABILITY_TMP) && mkdir -p $(DURABILITY_TMP)
	JAX_PLATFORMS=cpu \
	    $(PYTHON) -u bench/durability_selftest.py --out $(DURABILITY_TMP)

# The fused local-sort gate (ISSUE 17) — see bench/localsort_selftest.py.
# The third local engine (fused per-pass radix kernel + device-side
# merge-order kernel + planner key-width compaction) proven TPU-free:
# interpret-mode bit-identity vs lax across every codec dtype x input
# class (kernel AND api level, SORT_FALLBACK=0 so no silent degrade),
# one pallas_call per planned pass, narrow plans shorter than full
# width, the external-sort merge device-vs-host bit-identical, and the
# radix_compact policy's pass prediction honest (lying profiles stamp
# regret).  The final report pass schema-checks the emitted spans.
LOCALSORT_TMP := /tmp/mpitest_localsort_selftest
localsort-selftest:
	rm -rf $(LOCALSORT_TMP) && mkdir -p $(LOCALSORT_TMP)
	JAX_PLATFORMS=cpu \
	    SORT_TRACE=$(LOCALSORT_TMP)/trace.jsonl \
	    $(PYTHON) -u bench/localsort_selftest.py
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(LOCALSORT_TMP)/trace.jsonl

# The sort-doctor gate (ISSUE 16) — see bench/doctor_selftest.py.
# Every DOCTOR_RULES pathology is planted deterministically and must be
# diagnosed EXACTLY (right rule, evidence cited, knob suggested); a
# real clean run must produce zero findings; the in-process sentinel
# cells prove the full alert loop (serve.alert span -> bridged
# sort_alerts_total -> flight-recorder dump that itself passes the
# schema check).  The final report passes re-validate a planted trace
# and render its diagnosis through the public --doctor CLI.
DOCTOR_TMP := /tmp/mpitest_doctor_selftest
doctor-selftest:
	rm -rf $(DOCTOR_TMP) && mkdir -p $(DOCTOR_TMP)
	JAX_PLATFORMS=cpu \
	    $(PYTHON) -u bench/doctor_selftest.py --out $(DOCTOR_TMP)
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(DOCTOR_TMP)/skew_imbalance.jsonl \
	    $(DOCTOR_TMP)/deadline_burn.jsonl
	$(PYTHON) -m mpitest_tpu.report --doctor $(DOCTOR_TMP)/skew_imbalance.jsonl

# The wire-chaos gate (ISSUE 11) — see bench/chaos_serve_selftest.py.
# Real servers behind the chaos TCP proxy on a plain 1-device CPU
# backend: the faults live on the wire and in the dispatch thread, not
# in the device math.
CHAOS_TMP := /tmp/mpitest_chaos_selftest
chaos-serve-selftest:
	rm -rf $(CHAOS_TMP) && mkdir -p $(CHAOS_TMP)
	JAX_PLATFORMS=cpu \
	    $(PYTHON) -u bench/chaos_serve_selftest.py --out $(CHAOS_TMP)
	$(PYTHON) -m mpitest_tpu.report --check --require-registered-spans \
	    $(CHAOS_TMP)/server_trace_chaos.jsonl \
	    $(CHAOS_TMP)/server_trace_watchdog.jsonl
	$(PYTHON) -m mpitest_tpu.report \
	    $(CHAOS_TMP)/server_trace_watchdog.jsonl

# Proof the streamed ingest pipeline is live, overlapping, and fast
# (ISSUE 6): the NATIVE encode engine is built and FORCED ON for every
# leg.  Leg 1: a 2^22-key SORTBIN1 file (mmap-sliced into 16 chunks)
# sorted through the CLI on a virtual CPU mesh; the span stream must
# pass the schema check AND show nonzero parse/encode ∩ transfer
# overlap — a serialized pipeline fails the gate.  Leg 2:
# bench/ingest_selftest.py asserts the perf contract — native encode
# throughput >= 2x the Python engine's on this host, and
# sort_incl_ingest_mkeys_per_s >= 0.5 x sort_mkeys_per_s — and records
# both in a metrics sidecar; the final report pass re-checks the ratio
# gate from that sidecar (--require-ingest-overlap reads ingest_ratio).
INGEST_TMP := /tmp/mpitest_ingest_selftest
ingest-selftest: native-encode
	rm -rf $(INGEST_TMP) && mkdir -p $(INGEST_TMP)
	$(PYTHON) -c "import numpy as np; \
	    from mpitest_tpu.utils.io import write_keys_binary; \
	    write_keys_binary('$(INGEST_TMP)/keys.bin', \
	    np.random.default_rng(0).integers(-2**31, 2**31-1, size=1<<22, \
	    dtype=np.int32))"
	JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	    SORT_ALGO=radix SORT_RANKS=4 SORT_NATIVE_ENCODE=on \
	    SORT_INGEST=stream SORT_INGEST_CHUNK=262144 SORT_INGEST_THREADS=2 \
	    SORT_TRACE=$(INGEST_TMP)/trace.jsonl \
	    $(PYTHON) drivers/sort_cli.py $(INGEST_TMP)/keys.bin > /dev/null
	JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	    SORT_NATIVE_ENCODE=on \
	    SORT_METRICS=$(INGEST_TMP)/metrics.jsonl \
	    $(PYTHON) -u bench/ingest_selftest.py $(INGEST_TMP)/keys.bin
	$(PYTHON) -m mpitest_tpu.report --check --require-ingest-overlap \
	    $(INGEST_TMP)/trace.jsonl $(INGEST_TMP)/metrics.jsonl

# ---------------------------------------------------------------- lint
# The static-analysis gate (ISSUE 4).  Always-on legs: sortlint,
# threadlint (ISSUE 19: interprocedural concurrency analysis — JAX
# fence, lock order, blocking-under-lock, shared-write locksets, GIL
# wedge), the comm parity checker, and the C warning gate (gcc is in
# every image).  mypy / clang-tidy legs run when installed and report
# a loud SKIP otherwise — never a silent pass of a gate that did not
# run.
lint: cwarn-check
	$(PYTHON) -m tools.sortlint
	$(PYTHON) -m tools.threadlint
	$(PYTHON) tools/comm_parity.py
	$(MAKE) typecheck tidy-check

#: Fixture drift gate: every threadlint rule must still FIRE on its
#: planted bad fixture — a silently-dead rule is worse than no rule.
threadlint-fixtures:
	$(PYTHON) -m tools.threadlint --selftest

#: Every C source must compile warning-free under the strict set.  The
#: two MPI-linked files typecheck against the vendored stub header.
CWARN := -O2 -std=c11 -Wall -Wextra -Wconversion -Wshadow -Werror \
    -fsyntax-only
cwarn-check:
	$(CC) $(CWARN) -Icomm comm/comm_local.c
	$(CC) $(CWARN) -Icomm -Icomm/mpi_stub comm/comm_mpi.c
	$(CC) $(CWARN) -Icomm -Icomm/mpi_stub comm/mpi_stub/mpi_mock.c
	$(CC) $(CWARN) -Icomm -Icomm/mpi_stub comm/mpi_stub/minimpi.c
	$(CC) $(CWARN) -Icomm -Inative native/sample_sort.c
	$(CC) $(CWARN) -Icomm -Inative native/radix_sort.c
	$(CC) $(CWARN) -Icomm native/comm_selftest.c
	$(CC) $(CWARN) -Icomm native/comm_bench.c
	$(CC) $(CWARN) -Icomm native/comm_fuzz.c
	$(CC) $(CWARN) -Icomm/mpi_stub native/minimpi_earlyexit.c
	$(CC) $(CWARN) -Inative native/encode.c
	$(CC) $(CWARN) -Inative native/encode_fuzz.c
	$(CC) $(CWARN) -Inative native/spillz.c
	$(CC) $(CWARN) -Inative native/spillz_fuzz.c
	@echo "cwarn-check OK (-Wconversion -Wshadow -Werror clean)"

typecheck:
	@if $(PYTHON) -c "import mypy" 2>/dev/null; then \
	    $(PYTHON) -m mypy --config-file pyproject.toml && \
	    echo "mypy strict OK (typed core)"; \
	else \
	    echo "SKIP: mypy not installed (CI lint job runs it;" \
	         "sortlint SL040 enforces annotation completeness here)"; \
	fi

tidy-check:
	@if command -v clang-tidy >/dev/null 2>&1; then \
	    clang-tidy --quiet comm/comm_local.c native/sample_sort.c \
	        native/radix_sort.c -- -Icomm -Inative -std=c11 && \
	    echo "clang-tidy OK"; \
	else \
	    echo "SKIP: clang-tidy not installed (cwarn-check is the" \
	         "always-on C gate)"; \
	fi

knob-docs:
	$(PYTHON) tools/gen_knob_docs.py

# ---------------------------------------------------- sanitize-selftest
# The runtime half of the gate: build + RUN the comm selftest and a
# seeded, bounded fuzz run under each sanitizer.  Same seed must fold to
# the same checksum across sanitizer builds and backends (any divergence
# means a sanitizer-visible bug altered behavior).  bench/Makefile's
# build stamp rebuilds on SANITIZE changes for the BACKEND targets; the
# minimpi binaries are removed explicitly (they carry no stamp).
SAN_SEEDS := 1 42 1234
SAN_SUPP  := $(CURDIR)/tools/sanitize.supp
# checkout-scoped staging for the differential (NOT a shared /tmp path:
# a concurrent run in another checkout must not interleave with ours)
SAN_OUT   := $(CURDIR)/bench/.san-out
sanitize-selftest:
	@echo "== TSan: pthreads backend (race detector) =="
	mkdir -p $(SAN_OUT)
	$(MAKE) -C bench SANITIZE=thread BACKEND=local comm_selftest comm_fuzz
	TSAN_OPTIONS="suppressions=$(SAN_SUPP)" COMM_RANKS=4 ./bench/comm_selftest
	TSAN_OPTIONS="suppressions=$(SAN_SUPP)" COMM_RANKS=8 ./bench/comm_selftest
	# NOTE: fuzz output goes to a file, never through a pipe — `| tee`
	# would take tee's exit status and mask a sanitizer's nonzero exit,
	# which is the one signal this gate exists to propagate.
	for s in $(SAN_SEEDS); do \
	    TSAN_OPTIONS="suppressions=$(SAN_SUPP)" COMM_RANKS=5 \
	        ./bench/comm_fuzz $$s 200 > $(SAN_OUT)/tsan_$$s || exit 1; \
	    cat $(SAN_OUT)/tsan_$$s; \
	done
	@echo "== ASan+UBSan: pthreads backend =="
	$(MAKE) -C bench SANITIZE=address,undefined BACKEND=local \
	    comm_selftest comm_fuzz
	ASAN_OPTIONS="suppressions=$(SAN_SUPP)" COMM_RANKS=4 ./bench/comm_selftest
	for s in $(SAN_SEEDS); do \
	    ASAN_OPTIONS="suppressions=$(SAN_SUPP)" COMM_RANKS=5 \
	        ./bench/comm_fuzz $$s 200 > $(SAN_OUT)/asan_$$s || exit 1; \
	    cat $(SAN_OUT)/asan_$$s; \
	done
	@echo "== ASan+UBSan: native encode kernel fuzz (ISSUE 6) =="
	rm -f bench/encode_fuzz
	$(MAKE) -C bench SANITIZE=address,undefined encode_fuzz
	for s in $(SAN_SEEDS); do \
	    ASAN_OPTIONS="suppressions=$(SAN_SUPP)" \
	        ./bench/encode_fuzz $$s 300 > $(SAN_OUT)/encasan_$$s || exit 1; \
	    cat $(SAN_OUT)/encasan_$$s; \
	done
	rm -f bench/encode_fuzz
	$(MAKE) -C bench encode_fuzz
	# sanitized-vs-plain differential: same seed must fold to the same
	# checksum (UB the sanitizers altered would diverge here)
	for s in $(SAN_SEEDS); do \
	    ./bench/encode_fuzz $$s 300 > $(SAN_OUT)/encplain_$$s || exit 1; \
	    cmp $(SAN_OUT)/encasan_$$s $(SAN_OUT)/encplain_$$s || exit 1; \
	done
	@echo "== ASan+UBSan: spill block-codec fuzz, corrupt corpora (ISSUE 20) =="
	rm -f bench/spillz_fuzz
	$(MAKE) -C bench SANITIZE=address,undefined spillz_fuzz
	for s in $(SAN_SEEDS); do \
	    ASAN_OPTIONS="suppressions=$(SAN_SUPP)" \
	        ./bench/spillz_fuzz $$s 1500 > $(SAN_OUT)/spzasan_$$s || exit 1; \
	    cat $(SAN_OUT)/spzasan_$$s; \
	done
	rm -f bench/spillz_fuzz
	$(MAKE) -C bench spillz_fuzz
	for s in $(SAN_SEEDS); do \
	    ./bench/spillz_fuzz $$s 1500 > $(SAN_OUT)/spzplain_$$s || exit 1; \
	    cmp $(SAN_OUT)/spzasan_$$s $(SAN_OUT)/spzplain_$$s || exit 1; \
	done
	@echo "== ASan+UBSan: MPI backend over the fork-based minimpi runtime =="
	rm -f bench/comm_selftest_minimpi bench/comm_fuzz_minimpi
	$(MAKE) -C bench SANITIZE=address,undefined \
	    comm_selftest_minimpi comm_fuzz_minimpi
	ASAN_OPTIONS="suppressions=$(SAN_SUPP)" MINIMPI_NP=4 \
	    ./bench/comm_selftest_minimpi
	for s in $(SAN_SEEDS); do \
	    ASAN_OPTIONS="suppressions=$(SAN_SUPP)" MINIMPI_NP=5 \
	        ./bench/comm_fuzz_minimpi $$s 200 \
	        > $(SAN_OUT)/minimpi_$$s || exit 1; \
	    cat $(SAN_OUT)/minimpi_$$s; \
	done
	@echo "== cross-sanitizer / cross-backend checksum differential =="
	for s in $(SAN_SEEDS); do \
	    cmp $(SAN_OUT)/tsan_$$s $(SAN_OUT)/asan_$$s || exit 1; \
	    a=$$(grep -o 'checksum=.*' $(SAN_OUT)/asan_$$s); \
	    b=$$(grep -o 'checksum=.*' $(SAN_OUT)/minimpi_$$s); \
	    test "$$a" = "$$b" || { echo "checksum mismatch seed $$s"; exit 1; }; \
	done
	rm -f bench/comm_selftest_minimpi bench/comm_fuzz_minimpi
	$(MAKE) -C bench BACKEND=local  # restore unsanitized default builds
	@echo "sanitize-selftest OK (TSan + ASan/UBSan x both backends," \
	    "suppressions file empty)"

clean:
	$(MAKE) -C mpi_sample_sort clean
	$(MAKE) -C mpi_radix_sort clean
	$(MAKE) -C bench clean
	rm -rf $(SAN_OUT) $(CURDIR)/bench/.spill-out
