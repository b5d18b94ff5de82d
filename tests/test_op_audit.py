"""The op-coverage audit must stay clean: every operator type the
reference registers maps to a verified symbol, a delegation, or a
documented deferral (tools/op_audit.py)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = "/root/reference/paddle/fluid/operators"


@pytest.mark.skipif(not os.path.isdir(REF),
                    reason="reference tree not available")
def test_audit_has_zero_unmapped_ops():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "op_audit.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "UNMAPPED" not in r.stdout
    # the mapped-symbol count is the real coverage claim — keep it honest
    assert "symbol=4" in r.stdout or "symbol=5" in r.stdout, r.stdout
