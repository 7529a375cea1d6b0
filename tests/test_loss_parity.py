"""North-star criterion 2 (BASELINE.md): loss within 1% of the reference baseline.

`tools/loss_parity.py` trains the SAME weights on the SAME batch stream through both engines
(ours and /root/reference's torch model with the reference trainer's exact loss/clip/AdamW
semantics) and writes LOSS_PARITY.json. This test (a) runs a short live parity check, and
(b) asserts the committed 200-step artifact meets the 1% bar.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "LOSS_PARITY.json")


import importlib.util

import pytest


@pytest.mark.skipif(
    importlib.util.find_spec("dolomite_engine") is None,
    reason="torch reference engine (dolomite_engine) not installed in this environment",
)
def test_live_loss_parity_short(tmp_path):
    """25 fresh steps through both engines: gap must stay under 1% (it is ~0: identical
    weights + data + fp32 semantics differ only by reduction order)."""
    out = tmp_path / "parity.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "loss_parity.py"),
         "--steps", "25", "--out", str(out)],
        capture_output=True,
        text=True,
        timeout=1200,
        cwd=REPO,
        # the child is a CPU run however pytest itself was started
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(out))
    assert result["max_rel_gap"] < 0.01, result
    # (no learning assert: the synthetic corpus is near-uniform random tokens, so the loss
    # hovers at the ~ln(vocab) floor — the property under test is parity, not convergence)


import pytest


@pytest.mark.parametrize(
    "artifact", ["LOSS_PARITY.json", "LOSS_PARITY_moe_dolomite.json"]
)
def test_committed_parity_artifact(artifact):
    """The 200-step committed evidence (dense + MoE incl. aux loss): max per-step relative
    gap < 1%."""
    path = os.path.join(REPO, artifact)
    assert os.path.isfile(path), f"run tools/loss_parity.py to generate {artifact}"
    result = json.load(open(path))
    assert result["steps"] >= 200
    assert result["max_rel_gap"] < 0.01, (
        f"loss gap {result['max_rel_gap'] * 100:.3f}% exceeds the 1% north-star bar"
    )
    assert result["final_rel_gap"] < 0.01
