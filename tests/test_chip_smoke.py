"""Rehearsal of `chip_smoke.py` without the chip (on-chip-measurement
guide §2.1/§2.2): every phase runs here at a tiny size on the CPU — the
four-chip phase on four of the virtual devices, the pallas kernel in
interpret mode — so a wrong path, argument or sharding rule costs no chip
time. The steering (sizes, interpret mode) lives HERE, not in an option
of the script; the script itself only ever passes on a TPU.
"""
import functools
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_BERT = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 intermediate_size=128, max_position_embeddings=64,
                 hidden_dropout=0.0, attention_dropout=0.0)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_refuses_to_pass_without_a_tpu(argv):
    """On a CPU every invocation exits non-zero, before doing any work,
    and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        *argv], capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.mark.slow  # ~12 s: run this file unfiltered before any chip call
def test_train_phase_tiny(smoke, capsys):
    smoke.phase_train(cfg_kw=TINY_BERT, batch=4, seq=64)
    out = capsys.readouterr().out
    assert '"structure": "scan"' in out and '"structure": "unroll"' in out
    assert '"phase": "train_agreement"' in out


def test_kernel_case_tiny_interpret(smoke, monkeypatch):
    """The phase's case through the public dispatch, with the dispatch
    steered onto the interpreted kernel: forward and the three gradients
    agree with the float32 `_sdpa` path."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional import attention

    monkeypatch.setattr(fa, "is_available", lambda: True)
    monkeypatch.setattr(fa, "flash_attention_bshd", functools.partial(
        fa.flash_attention_bshd, interpret=True))
    monkeypatch.setattr(attention, "_FLASH_MIN_SEQ", 128)
    for causal in (True, False):
        _calls, errs = smoke.kernel_case(1, 128, 2, 64, causal)
        assert set(errs) == {"out", "dq", "dk", "dv"}
        assert max(errs.values()) <= smoke.KERNEL_TOL, errs


def test_serve_phase_tiny(smoke, capsys):
    smoke.phase_serve(feat=16, hidden=32, ladder=(1, 4, 16),
                      rows=(1, 2, 3, 5, 16))
    assert '"request_path_compiles": 0' in capsys.readouterr().out


@pytest.mark.slow  # ~9 s: run this file unfiltered before any chip call
def test_zero3_dp4_phase_tiny(smoke, capsys):
    import gc
    gc.collect()  # earlier tests' sharded stores must not ride this mesh
    # 64-wide tensors pad to 1024-lane rows: padding dominates down here
    smoke.phase_zero3_dp4(cfg_kw=TINY_BERT, batch=8, seq=64,
                          state_share_max=0.5)
    assert '"store_device_spans": [4]' in capsys.readouterr().out
