"""Quickstart: SEARS as a file store -- upload, dedup, code, fail, restore.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.classes import StorageClass
from repro.core.store import SEARSStore


def main() -> None:
    # a 4-cluster SEARS deployment: one (n=10, k=5) ULB storage class.
    # engine="fused" runs put windows through the single-launch
    # hash+encode mega-kernel (engine="numpy"/"kernel" are byte-identical).
    store = SEARSStore(
        classes=[StorageClass(name="default", n=10, k=5, binding="ulb")],
        num_clusters=4, node_capacity=1 << 30, engine="fused")

    rng = np.random.default_rng(0)
    report = rng.integers(0, 256, size=300_000, dtype=np.int64).astype(
        np.uint8).tobytes()

    # --- upload: chunked, hashed, deduped, erasure coded -----------------
    st = store.put_file("alice", "report.doc", report)
    print(f"upload: {st.n_chunks} chunks, {st.n_new_chunks} new, "
          f"{st.bytes_uploaded / 1e3:.0f} kB sent, "
          f"{st.piece_bytes_written / 1e3:.0f} kB stored (n/k = 2x)")

    # --- duplicate content costs nothing ---------------------------------
    st2 = store.put_file("alice", "report-final.doc", report)
    print(f"re-upload: {st2.n_new_chunks} new chunks, "
          f"{st2.bytes_uploaded} bytes sent (dedup)")

    # --- a backlog through the scheduler: one shared put window ---------
    # queued requests coalesce into flush windows that share one gear,
    # one SHA-1 and one GF batch per length bucket across every request
    sched = store.scheduler()
    parts = [(f"batch{w}/part{i}",
              rng.integers(0, 256, size=60_000, dtype=np.int64)
              .astype(np.uint8).tobytes())
             for w in range(3) for i in range(3)]
    puts = [sched.submit_put("bob", [part]) for part in parts]
    sched.flush()
    print(f"scheduled ingest: {len(puts)} puts in "
          f"{sched.stats.n_put_windows} window, "
          f"{sum(s.n_chunks for f in puts for s in f.result())} chunks")

    # --- half the storage nodes die; the files survive -------------------
    for cluster in store.clusters:
        cluster.kill_nodes([0, 2, 4, 6, 8])
    data, rst = store.get_file("alice", "report.doc")
    assert data == report
    print(f"retrieval with 5/10 nodes dead: OK, modeled {rst.time_s:.2f}s "
          f"({rst.n_fetched} chunks from {rst.clusters_touched} cluster)")

    # --- a degraded multi-file get window: one batched decode -----------
    got = sched.submit_get("bob", [name for name, _ in parts])
    sched.flush()
    results = got.result()
    assert [data for data, _ in results] == [blob for _, blob in parts]
    print(f"scheduled degraded get: {len(results)} files OK, "
          f"mean modeled {np.mean([r.time_s for _, r in results]):.2f}s")

    # --- storage accounting ------------------------------------------------
    s = store.stats()
    print(f"dedup ratio (logical/consumed incl. index): {s.dedup_ratio:.2f}")


if __name__ == "__main__":
    main()
