PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-slow test-fast test-launches test-shards test-cache \
	lint bench headline

# tier-1 verification command (slow interpret-mode kernel tests are
# deselected by pytest.ini; run them with `make test-slow`)
test:
	$(PYTHON) -m pytest -x -q

# the slow interpret-mode Pallas kernel sweeps only
test-slow:
	$(PYTHON) -m pytest -x -q -m slow

# dispatch-regression lane (also a CI job): a put window must stay
# O(1) gear + O(1) SHA-1 + O(buckets) GF launches with no gear retraces,
# a storm repair pass must stay O(buckets) per sub-batch, not O(chunks)
# (including whole-cluster re-placement drains and scrub sweeps), and a
# mixed-storage-class window must stay O(code buckets x length buckets),
# never O(files)
test-launches:
	$(PYTHON) -m pytest -x -q tests/test_ingest.py tests/test_repair.py \
		tests/test_classes.py tests/test_disaster.py

# sharded-control-plane lane: ShardMap mechanics + the N-shard-vs-
# 1-shard differential proof harness (all engines, mid-trace add/drain),
# then the core store/scheduler suites re-run sanitized with 3 control
# shards so the per-shard launch model and shard-ledger conservation
# checks run live on every window
test-shards:
	$(PYTHON) -m pytest -x -q tests/test_shards.py
	SEARS_SANITIZE=1 SEARS_SHARDS=3 $(PYTHON) -m pytest -x -q \
		tests/test_store.py tests/test_scheduler.py

# block-cache lane: BlockCache mechanics, write-back ack/drain/delete
# ordering, shard-drain + cluster-loss barriers, scheduler priority
# lanes + admission control, and the cache-on-vs-off differential
# proof -- then the whole suite again with the runtime sanitizer's
# cache-ledger audit live on every window
test-cache:
	$(PYTHON) -m pytest -x -q tests/test_cache.py
	SEARS_SANITIZE=1 $(PYTHON) -m pytest -x -q tests/test_cache.py

# searslint: begin-purity, dispatch hygiene, counter coverage, plan
# determinism, cache discipline (exits 1 on any unwaivered finding)
lint:
	$(PYTHON) -m repro.lint src tests benchmarks

# skip the slow model/kernel suites; storage core only
test-fast:
	$(PYTHON) -m pytest -x -q tests/test_store.py tests/test_engine.py \
		tests/test_scheduler.py tests/test_ingest.py \
		tests/test_repair.py tests/test_classes.py \
		tests/test_disaster.py \
		tests/test_gf256_rs.py tests/test_chunking_hashing.py \
		tests/test_workload_binding.py tests/test_system.py \
		tests/test_lint.py tests/test_sanitizer.py tests/test_shards.py \
		tests/test_cache.py

# paper-figure battery (fig3a-d + headline_3mb): the paper's modelled
# behaviour checked on the CPU, CSV on stdout; speed is measured on the
# chip by bench/run.py (BENCHMARK.json), not here
bench:
	$(PYTHON) -m benchmarks.run

# headline 3 MB retrieval claim; ENGINE=numpy|kernel|fused
ENGINE ?= numpy
headline:
	$(PYTHON) benchmarks/headline_3mb.py --engine $(ENGINE)
