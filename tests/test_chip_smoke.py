"""The phases of ``chip_smoke.py`` at a tiny size on the CPU.

On the CPU the Pallas kernels run in interpret mode, which the device gate
refuses; these tests call the phases past the gate.  The gate itself is
checked to refuse the CPU, a forced interpret mode, and an interpret-mode
probe, and ``main`` to exit non-zero without the result line.
"""

import os
import sys
from types import SimpleNamespace

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.kernels import ops  # noqa: E402


def test_main_refuses_cpu(capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ""                       # no phase line, no result
    assert "no TPU" in out.err
    # the gate fails before the compile cache is touched
    assert jax.config.jax_compilation_cache_dir == cache_dir


def _fake_tpu(monkeypatch):
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


@pytest.mark.parametrize("forced", [True, False])
def test_gate_refuses_interpret_mode(monkeypatch, forced):
    """A TPU with interpret mode forced by the environment, or chosen by
    the backend probe, is refused too."""
    _fake_tpu(monkeypatch)
    if forced:
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    else:
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
        monkeypatch.setattr(ops, "_default_interpret", lambda: True)
    with pytest.raises(chip_smoke.SmokeError, match="interpret"):
        chip_smoke.device_gate()


def test_gate_passes_compiled_tpu(monkeypatch):
    _fake_tpu(monkeypatch)
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    assert chip_smoke.device_gate() == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_phases_tiny(tmp_path):
    """Load → serve → bulk → crash at 20K rows: every check of every phase
    holds (each phase raises SmokeError otherwise)."""
    d = str(tmp_path)
    be, r = chip_smoke.load_phase(20_000, d)
    assert r["rows"] == 20_000 and len(be.table) == 20_000
    acked, r = chip_smoke.serve_phase(be, n_txn=300, rate=3000.0,
                                      max_batch=16)
    assert r["acked"] == r["submitted"] == 300
    assert r["rejected"] == r["exec_errors"] == 0
    assert r["occ_seg_reduce_compiles"] > 0
    more, r = chip_smoke.bulk_phase(be, n_batches=6, batch_txns=4096)
    assert r["fused_rounds"] == 6
    assert r["compiles"]["fused_validate_sequence"] > 0
    r = chip_smoke.crash_phase(be, d, acked + more, batch_txns=4096)
    assert r["fused"] and r["images_equal"] and r["acked_missing"] == 0
    assert min(r["sealed_segments"]) >= 2
