# Dev workflow (the reference's Makefile orchestrates kind clusters and
# kustomize deploys; standalone TPU-native operation needs only python).

PY ?= python

.PHONY: test test-unit test-e2e test-stress smoke run run-multi lint lint-acp \
	chaos-smoke chaos-soak \
	dryrun ci docker-build docker-run observability-up observability-down

IMG ?= acp-tpu:dev
JAX_EXTRA ?=

docker-build:  ## build the operator+engine image (JAX_EXTRA=tpu for TPU VMs)
	docker build -f deploy/Dockerfile --build-arg JAX_EXTRA=$(JAX_EXTRA) -t $(IMG) .

docker-run:  ## serve BASELINE config 1 shape locally (REST on :8080)
	docker run --rm -p 8080:8080 $(IMG)

observability-up:  ## otel-collector + prometheus + grafana (dashboard: ACP-TPU)
	docker compose -f deploy/observability/docker-compose.yaml up -d

observability-down:
	docker compose -f deploy/observability/docker-compose.yaml down

test:
	$(PY) -m pytest tests/ -x -q

test-unit:
	$(PY) -m pytest tests/ -x -q --ignore=tests/e2e

test-e2e:
	$(PY) -m pytest tests/e2e -x -q

test-stress:
	ACP_STRESS=1 $(PY) -m pytest tests/e2e/test_tpu_provider.py -k test_64_concurrent_tasks_stress -x -q

smoke:  ## does provider: tpu still start on the chip? (one TPU; exits non-zero without one)
	$(PY) chip_smoke.py

chaos-smoke:  ## one seeded fault cocktail against a live 3-replica fleet, invariants gated (fast CI tier)
	$(PY) -m agentcontrolplane_tpu.cli chaos --seed 3 --gate --replicas 3 --speed 20 \
	  --set n=8 --tpu-preset tiny --tpu-slots 4 --tpu-ctx 64 --tpu-kv-layout paged --no-prewarm

chaos-soak:  ## multi-seed chaos soak + the rest of the slow tier's chaos coverage
	$(PY) -m pytest tests/scenarios/test_chaos.py -q -m slow

dryrun:
	$(PY) -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

run:
	$(PY) -m agentcontrolplane_tpu.cli run --db acp-state.db

run-multi:  ## two-replica dev control plane: owner serves the store, follower joins
	@sh -c '$(PY) -m agentcontrolplane_tpu.cli run --db acp-state.db \
	  --serve-store unix:///tmp/acp-store.sock --identity owner & \
	  owner=$$!; trap "kill $$owner 2>/dev/null" EXIT INT TERM; \
	  sleep 2 && $(PY) -m agentcontrolplane_tpu.cli run \
	  --store unix:///tmp/acp-store.sock --identity follower --port 8083'

lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check agentcontrolplane_tpu tests; \
	else \
		echo "ruff not installed; falling back to compileall (syntax only)"; \
		$(PY) -m compileall -q agentcontrolplane_tpu tests; \
	fi

# pinned gates: ACP_LINT_SUPPRESSIONS is the live '# acp-lint: disable='
# count (growth fails with the justification list — raise it only in the
# PR that adds the pragma); ACP_LINT_BUDGET_S bounds the whole pass pack's
# wall time on a bare checkout so a rule can't silently become the slow
# CI step (current full run ~4s; 30s leaves cold-cache headroom).
ACP_LINT_SUPPRESSIONS ?= 4
ACP_LINT_BUDGET_S ?= 30

lint-acp:  ## repo-custom static analysis (acplint) — the engine's correctness contracts
	$(PY) -m agentcontrolplane_tpu.analysis --metrics-docs docs/observability.md \
		--faults-docs \
		--timing --timing-budget $(ACP_LINT_BUDGET_S) \
		--suppression-budget $(ACP_LINT_SUPPRESSIONS) \
		--json acplint-findings.json \
		agentcontrolplane_tpu tests

ci: lint lint-acp test dryrun
