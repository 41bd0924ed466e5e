"""CPU rehearsal of ``chip_smoke.py``: its checked path at a tiny size.

Off the chip the device engine resolves to the jitted jnp oracles, so
this proves the phases, the reference comparisons and the checks, not
the Pallas kernels (``test_tpu_compile.py`` compiles those).
"""

import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(n_users=1, hot_files=1, cold_files=2, file_kb=12,
            flush_bytes=1 << 20, num_clusters=2)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _quiet(*_):
    pass


def test_smoke_path_passes_at_tiny_size(smoke):
    res = smoke.run(smoke.SmokeConfig(**TINY), log=_quiet)
    assert res["impl"] == "ref"  # off the chip: the jnp oracles
    assert res["pieces_rebuilt"] > 0
    launches = res["launches"]
    assert launches.gear and launches.sha1 and launches.gf


def test_smoke_checks_fail_when_bytes_differ(smoke, monkeypatch):
    from repro.core.cluster import StorageNode

    real = StorageNode.get

    def flipped(self, chunk_id, piece_idx):
        piece = real(self, chunk_id, piece_idx)
        if self.node_id == 0 and piece:
            return bytes([piece[0] ^ 1]) + piece[1:]
        return piece

    monkeypatch.setattr(StorageNode, "get", flipped)
    cfg = smoke.SmokeConfig(**TINY)
    with pytest.raises(smoke.SmokeError, match="bytes differ"):
        smoke.drive("numpy", smoke.traffic(cfg), cfg, log=_quiet)


def test_smoke_main_refuses_cpu(smoke, monkeypatch, capsys, tmp_path):
    # the env var keeps the compile-cache call from pointing this
    # process's cache anywhere
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
