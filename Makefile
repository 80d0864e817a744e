# Test entry points (README.md "Tests").
#
# tier1        — ROADMAP.md's tier-1 verify, verbatim (tools/tier1.sh):
#                the whole suite on the CPU backend; prints
#                DOTS_PASSED=<n> at the end.  Runs tier1-lint first.
# tier1-lint   — metrics/docs parity (tools/check_metrics_docs.py):
#                every registered crane_* metric has a row in the
#                ARCHITECTURE.md metric inventory table and vice-versa.
# tier1-commit — commit-path lane: WAL recovery/group-commit, commit and
#                dispatch-ring tests and the per-route WAL counts only —
#                seconds, not minutes.  Use while iterating on wal.py,
#                _commit, or the dispatcher fan-out.
# tier1-<mark> — one lane a marker of pytest.ini (obs, ha, topo, delta,
#                resident, jobtrace, fed, flight, rebalance): the tests
#                that carry @pytest.mark.<mark>, e.g. `make tier1-obs`
#                while iterating on obs/, `make tier1-ha` for the
#                leader+standby failover drill.
#
# How fast the system is is not a test's business: that is measured on
# the chip by benchmark/run.py (README.md "Measuring", PERF.md).

PYTEST = env JAX_PLATFORMS=cpu python -m pytest -q \
	-p no:cacheprovider -p no:xdist -p no:randomly

.PHONY: tier1 tier1-lint tier1-commit

tier1: tier1-lint
	bash tools/tier1.sh

tier1-lint:
	python tools/check_metrics_docs.py

tier1-commit:
	$(PYTEST) tests/test_wal_recovery.py tests/test_commit_dispatch.py \
	  tests/test_cycle_counts.py -m "not slow"

tier1-%:
	$(PYTEST) tests/ -m $*
