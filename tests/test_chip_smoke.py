"""chip_smoke.py's phases on the CPU at a tiny corpus, its checks and their
failures, and its refusal to report success off the chip.

The phases run with ``REPRO_FORCE_INTERPRET=1``, so the serving path goes
through the same Pallas kernels as on the chip, in interpret mode.
"""

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def test_phases_on_tiny_corpus(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    summary = cs.run_one_chip(0, n_items=400, m_users=600, d=16,
                              ranks=(2, 4, 8, 16), kernels=False)
    assert summary["tiles_scanned"] > 0
    for tenant in cs.REVERSE_TENANTS:
        assert summary[f"f1_{tenant}"] >= cs.F1_FLOOR
    assert summary["recall_forward"] >= cs.RECALL_FLOOR


_LOWERED = (
    '%3 = stablehlo.custom_call @tpu_custom_call(%0, %2) {backend_config = '
    '"...", kernel_name = "hamming_scores", kernel_metadata = "{}"}\n'
    '%7 = stablehlo.custom_call @tpu_custom_call(%5) {kernel_name = '
    '"srp_hash"}\n'
    '%9 = stablehlo.dot_general %1, %2\n')


def test_kernel_names_from_lowered_text():
    assert cs.tpu_kernels(_LOWERED) == {"hamming_scores", "srp_hash"}
    cs.check_kernels({"reverse-f32": _LOWERED, "forward": _LOWERED,
                      "reverse-int8": _LOWERED.replace("hamming_scores",
                                                       "fused_scan")})


@pytest.mark.parametrize("failing", [
    lambda: cs.check_floor("reverse mean F1", 0.89, cs.F1_FLOOR),
    lambda: cs.check_floor("forward recall@k", 0.5, cs.RECALL_FLOOR),
    lambda: cs.check_floor("reverse mean F1", float("nan"), cs.F1_FLOOR),
    lambda: cs.check_tiles(0),
    lambda: cs.check_kernels({p: _LOWERED for p in cs.PROGRAM_KERNELS}),
], ids=["f1", "recall", "nan", "no_tiles", "missing_kernel"])
def test_checks_fail(failing):
    with pytest.raises(cs.SmokeFailure):
        failing()


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_entry_point_fails_off_the_chip(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=script.parent)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
