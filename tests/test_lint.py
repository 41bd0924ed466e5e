"""searslint: each pass catches its seeded bad-code fixture, the real
tree is clean, and waivers work (with reasons required)."""

import pathlib

from repro.lint import run_paths, run_program
from repro.lint.core import Program, module_from_source

ROOT = pathlib.Path(__file__).resolve().parents[1]


def lint_sources(sources: dict[str, str]):
    """Run the full pass suite over {virtual_path: source} fixtures."""
    prog = Program([module_from_source(src, path)
                    for path, src in sources.items()])
    return run_program(prog)


def live(findings, rule=None):
    return [f for f in findings if not f.waived
            and (rule is None or f.rule == rule)]


# ------------------------------------------------------- begin purity ----

def test_begin_purity_catches_attribute_mutation():
    findings = lint_sources({"src/repro/core/engine.py": """
class Eng:
    def chunk_blobs_multi_begin(self, jobs):
        self.cache = jobs
        return jobs
"""})
    assert len(live(findings, "begin-purity")) == 1


def test_begin_purity_follows_call_graph_to_mutating_helper():
    findings = lint_sources({"src/repro/core/engine.py": """
class Eng:
    def _stash(self, jobs):
        self.table.append(jobs)

    def chunk_blobs_multi_begin(self, jobs):
        self._stash(jobs)
        return jobs
"""})
    hits = live(findings, "begin-purity")
    assert hits and "_stash" in hits[0].message


def test_begin_purity_catches_mutating_api_across_modules():
    findings = lint_sources({
        "src/repro/core/rs_code.py": """
from repro.core import helpers

def batch_decode_blobs_begin(code, jobs):
    helpers.record(jobs)
    return jobs
""",
        "src/repro/core/helpers.py": """
CACHE = {}

def record(jobs):
    CACHE['last'] = jobs
"""})
    hits = live(findings, "begin-purity")
    assert hits and "CACHE" in hits[0].message


def test_begin_purity_allows_locals_and_counters():
    findings = lint_sources({"src/repro/core/engine.py": """
from repro.kernels.launches import LAUNCHES

def chunk_blobs_begin(jobs):
    LAUNCHES.gear += 1
    groups = {}
    out = []
    for j in jobs:
        groups.setdefault(len(j), []).append(j)
        out.append(j)
    return out
"""})
    assert not live(findings, "begin-purity")


# --------------------------------------------------- dispatch hygiene ----

def test_dispatch_catches_jit_in_loop():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import jax

def run(fns, x):
    outs = []
    for f in fns:
        outs.append(jax.jit(f)(x))
    return outs
"""})
    assert len(live(findings, "dispatch-jit-loop")) == 1


def test_dispatch_catches_function_scope_jit():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import jax

def helper(x):
    return x

def make():
    return jax.jit(helper)
"""})
    assert len(live(findings, "dispatch-jit-scope")) == 1


def test_dispatch_module_scope_jit_is_fine():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import jax

def helper(x):
    return x

helper_jit = jax.jit(helper)
"""})
    assert not live(findings, "dispatch-jit-scope")
    assert not live(findings, "dispatch-jit-loop")


def test_dispatch_catches_unmemoized_constant_upload():
    bad = {"src/repro/kernels/ops.py": """
import jax.numpy as jnp

TABLE = [1, 2, 3]

def hot(x):
    t = jnp.asarray(TABLE)
    return t
"""}
    assert len(live(lint_sources(bad), "dispatch-const-asarray")) == 1
    memoized = {"src/repro/kernels/ops.py": """
import functools
import jax.numpy as jnp

TABLE = [1, 2, 3]

@functools.lru_cache(maxsize=None)
def device_table():
    return jnp.asarray(TABLE)
"""}
    assert not live(lint_sources(memoized), "dispatch-const-asarray")


def test_dispatch_catches_host_sync_in_begin_path():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import numpy as np

def gear_fire_issue(data):
    return data

def chunk_window_begin(data):
    fire = gear_fire_issue(data)
    fire.block_until_ready()
    return np.asarray(fire)
"""})
    assert len(live(findings, "dispatch-host-sync")) == 2


# --------------------------------------------------- counter coverage ----

def test_counters_catch_uncounted_launch_site():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import jax
from repro.kernels.launches import LAUNCHES, TRACES

@jax.jit
def _padded(x):
    TRACES.gf += 1
    return x

def apply(x):
    return _padded(x)
"""})
    hits = live(findings, "counter-launch")
    assert hits and "apply" in hits[0].message


def test_counters_accept_counted_call_sites():
    findings = lint_sources({
        "src/repro/kernels/gear_cdc.py": """
import jax
from repro.kernels.launches import TRACES

@jax.jit
def _padded(x):
    TRACES.gear += 1
    return x

def fire(x):
    return _padded(x)
""",
        "src/repro/kernels/ops.py": """
from repro.kernels import gear_cdc
from repro.kernels.launches import LAUNCHES

def issue(x):
    LAUNCHES.gear += 1
    return gear_cdc.fire(x)
"""})
    assert not live(findings, "counter-launch")


def test_counters_catch_traced_body_without_traces():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import jax

@jax.jit
def _padded(x):
    return x
"""})
    assert len(live(findings, "counter-trace")) == 1


def test_counters_catch_jit_alias_of_uncounted_lambda():
    findings = lint_sources({"src/repro/kernels/ops.py": """
import jax

apply = jax.jit(lambda x: x + 1)
"""})
    hits = live(findings, "counter-trace")
    assert hits and "apply" in hits[0].message


def test_counters_catch_single_family_reset():
    findings = lint_sources({"benchmarks/foo_bench.py": """
from repro.kernels.launches import LAUNCHES

LAUNCHES.reset()
"""})
    hits = live(findings, "counter-family-reset")
    assert hits and "reset_all" in hits[0].message


# -------------------------------------------------- plan determinism ----

def test_determinism_catches_set_iteration_in_placement():
    findings = lint_sources({"src/repro/core/store.py": """
def place(self, cluster_ids):
    for cl in set(cluster_ids):
        self.assign(cl)
"""})
    assert len(live(findings, "plan-determinism")) == 1


def test_determinism_catches_set_returning_api_and_set_local():
    findings = lint_sources({"src/repro/core/repair.py": """
def scan(self, cluster_id):
    out = []
    pool = {1, 2, 3}
    for cid in self.store.index.cluster_chunks(cluster_id):
        out.append(cid)
    for cl in pool:
        out.append(cl)
    return out
"""})
    assert len(live(findings, "plan-determinism")) == 2


def test_determinism_catches_shard_membership_iteration():
    """ShardMap.shards iteration (plain or via dict views) feeds routing
    order from add/drain insertion order — flagged unless sorted."""
    findings = lint_sources({"src/repro/core/shard.py": """
def route(self):
    for sid in self.shard_map.shards:
        self.touch(sid)
    out = [sh for sh in self.shard_map.shards.values()]
    return out
"""})
    assert len(live(findings, "plan-determinism")) == 2


def test_determinism_sorted_shard_iteration_is_clean():
    findings = lint_sources({"src/repro/core/store.py": """
def route(self):
    for sid in sorted(self.shard_map.shards):
        self.touch(sid)
    ok = 3 in self.shard_map.shards  # membership, not iteration
    return ok
"""})
    assert not live(findings, "plan-determinism")


def test_determinism_sorted_wrapping_and_membership_are_fine():
    findings = lint_sources({"src/repro/core/repair.py": """
def scan(self, cluster_id, scope):
    out = []
    for cid in sorted(self.store.index.cluster_chunks(cluster_id)):
        if cid in set(scope):
            out.append(cid)
    return out
"""})
    assert not live(findings, "plan-determinism")


# -------------------------------------------------- cache discipline ----

def test_cache_unbounded_catches_evictionless_attr_cache():
    findings = lint_sources({"src/repro/core/store.py": """
class Store:
    def __init__(self):
        self._chunk_cache = {}

    def get(self, cid):
        if cid not in self._chunk_cache:
            self._chunk_cache[cid] = self.decode(cid)
        return self._chunk_cache[cid]
"""})
    hits = live(findings, "cache-unbounded")
    assert hits and "_chunk_cache" in hits[0].message


def test_cache_unbounded_catches_module_level_dict():
    findings = lint_sources({"src/repro/core/helpers.py": """
PLAN_CACHE: dict = {}

def plan(key, fn):
    if key not in PLAN_CACHE:
        PLAN_CACHE[key] = fn()
    return PLAN_CACHE[key]
"""})
    assert len(live(findings, "cache-unbounded")) == 1


def test_cache_unbounded_allows_evicting_and_local_caches():
    findings = lint_sources({"src/repro/core/store.py": """
from collections import OrderedDict

class Store:
    def __init__(self):
        self._blob_cache = OrderedDict()

    def fill(self, cid, blob):
        self._blob_cache[cid] = blob
        while len(self._blob_cache) > 64:
            self._blob_cache.popitem(last=False)

    def plan(self, cids):
        cached: dict = {}   # per-call local, dies with the request
        for cid in cids:
            cached[cid] = self.peek(cid)
        return cached
"""})
    assert not live(findings, "cache-unbounded")


def test_cache_unbounded_ignores_non_storage_modules():
    findings = lint_sources({"src/repro/models/embed.py": """
ACTIVATION_CACHE = {}
"""})
    assert not live(findings, "cache-unbounded")


def test_cache_bypass_catches_direct_read_in_store():
    findings = lint_sources({"src/repro/core/store.py": """
def fetch(self, cluster, cids):
    return cluster.read_pieces_batch(cids, cluster.k)
"""})
    hits = live(findings, "cache-bypass")
    assert hits and "_read_cluster_pieces" in hits[0].message


def test_cache_bypass_allows_funnel_and_repair_modules():
    findings = lint_sources({
        "src/repro/core/store.py": """
def _read_cluster_pieces(self, cluster_id, chunk_ids):
    cluster = self.clusters[cluster_id]
    return cluster.read_pieces_batch(chunk_ids, cluster.k)
""",
        "src/repro/core/repair.py": """
def drain(self, cluster, cid):
    return cluster.read_pieces(cid, cluster.k)
"""})
    assert not live(findings, "cache-bypass")


def test_cache_bypass_waiver_with_reason_is_honored():
    findings = lint_sources({"src/repro/core/scheduler.py": """
def rebuild(self, cluster, cid):
    # searslint: ignore[cache-bypass] -- local rebuild, no time charged
    return cluster.read_pieces(cid, cluster.k)
"""})
    assert not live(findings)
    assert any(f.waived for f in findings)


# ------------------------------------------------------------ waivers ----

def test_waiver_with_reason_suppresses_finding():
    findings = lint_sources({"src/repro/core/store.py": """
def place(self, cluster_ids):
    # searslint: ignore[plan-determinism] -- order-insensitive census
    for cl in set(cluster_ids):
        self.census(cl)
"""})
    assert not live(findings)
    assert any(f.waived for f in findings)


def test_waiver_without_reason_is_a_finding():
    # Assemble the reasonless marker at runtime so the tree-wide scan of
    # this very file doesn't trip over the fixture text.
    marker = "# sears" + "lint: ignore[plan-determinism]"
    findings = lint_sources({"src/repro/core/store.py": f"""
def place(self, cluster_ids):
    for cl in set(cluster_ids):  {marker}
        self.census(cl)
"""})
    assert live(findings, "bad-waiver")


# --------------------------------------------------------- real tree ----

def test_current_tree_is_clean():
    findings = run_paths([ROOT / "src", ROOT / "tests", ROOT / "benchmarks"])
    assert not live(findings), "\n".join(
        f.format() for f in live(findings))


def test_tree_fixture_seeded_begin_mutation_is_caught():
    """Mutating the real engine.py (as a fixture copy) trips the pass —
    the clean verdict above is not vacuous."""
    engine_src = (ROOT / "src/repro/core/engine.py").read_text()
    mutated = engine_src.replace(
        "def chunk_blobs_multi_begin(self, jobs",
        "def chunk_blobs_multi_begin(self, jobs_, *, _x=None):\n"
        "        self._last_window = jobs_\n"
        "        jobs = jobs_\n"
        "        return self.chunk_blobs_multi_begin_real(jobs)\n\n"
        "    def chunk_blobs_multi_begin_real(self, jobs", 1)
    findings = lint_sources({"src/repro/core/engine.py": mutated})
    assert any("chunk_blobs_multi_begin" in f.message
               for f in live(findings, "begin-purity"))
