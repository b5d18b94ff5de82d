"""Config-4 flagship: the real GPT model through the fused dp x pp 1F1B
pipeline, loss+grad parity against the model's own eager tape path.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                   build_gpt_1f1b_step)


def _model():
    paddle.seed(5)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=2,
                    max_seq_len=16, hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()  # deterministic (no dropout) for parity
    return m


def _batches(M, mb, T, vocab):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (M, mb, T)).astype(np.int32)
    return ids


class TestGPT1F1BFlagship:
    @pytest.mark.slow  # ~17 s (PR 11 budget); 1F1B parity stays tier-1
    def test_loss_and_grads_match_eager(self):  # via the dropout-replay
        # parity case below and test_pipeline_1f1b's parity matrix
        m = _model()
        mesh = dist.make_mesh({"pp": 4})
        step, (stacked, first_p, last_p, leaf_names) = build_gpt_1f1b_step(
            m, mesh)
        M, mb, T = 4, 2, 8
        ids = _batches(M, mb, T, m.config.vocab_size)

        loss, (gP, gF, gL) = step(ids, ids)
        loss_pp = float(np.asarray(loss))

        # eager reference: same model, same microbatches, tape autograd
        losses = []
        for i in range(M):
            logits = m(Tensor(ids[i]))
            l = m.loss(logits, Tensor(ids[i])) / M
            l.backward()
            losses.append(float(np.asarray(l._value)) * M)
        loss_ref = float(np.mean(losses))
        np.testing.assert_allclose(loss_pp, loss_ref, rtol=1e-4)

        # block grads: stacked [pp, per, ...] vs per-block tape grads
        per = m.config.num_layers // 4
        qkv_idx = leaf_names.index("qkv.weight")
        for s in range(4):
            for i in range(per):
                blk = m.gpt.blocks[s * per + i]
                np.testing.assert_allclose(
                    np.asarray(gP[qkv_idx][s, i]),
                    np.asarray(blk.qkv.weight._grad), rtol=2e-3, atol=1e-5)

        # tied embedding: first-stage + head contributions
        wte_g = np.asarray(gF[0]) + np.asarray(gL[2])
        np.testing.assert_allclose(wte_g,
                                   np.asarray(m.gpt.wte.weight._grad),
                                   rtol=2e-3, atol=1e-5)

    def test_params_snapshot_tracks_updates(self):
        """step must see updated weights when given a fresh snapshot (the
        build-time snapshot is immutable by design)."""
        m = _model()
        mesh = dist.make_mesh({"pp": 4})
        step, _ = build_gpt_1f1b_step(m, mesh)
        ids = _batches(2, 2, 8, m.config.vocab_size)
        l0 = float(np.asarray(step(ids, ids)[0]))
        # perturb a block weight, re-snapshot
        blk = m.gpt.blocks[1]
        blk.qkv.weight.set_value(np.asarray(blk.qkv.weight.numpy()) * 2.0)
        l_stale = float(np.asarray(step(ids, ids)[0]))
        l_fresh = float(np.asarray(
            step(ids, ids, params=step.snapshot_params())[0]))
        assert l_stale == l0  # stale snapshot: unchanged (documented)
        assert l_fresh != l0  # fresh snapshot sees the update

    def test_train_mode_dropout_deterministic_per_key(self):
        """Train-mode dropout is supported via RNG-key threading (was a
        hard error before round 3): the same rng_key reproduces the same
        loss, a different key draws different masks."""
        import jax
        paddle.seed(5)
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=16, hidden_dropout=0.1,
                        attention_dropout=0.0)
        m = GPTForCausalLM(cfg)  # train mode, dropout>0
        mesh = dist.make_mesh({"pp": 4})
        step, _ = build_gpt_1f1b_step(m, mesh)
        ids = _batches(4, 2, 8, cfg.vocab_size)
        l1 = float(np.asarray(step(ids, ids,
                                   rng_key=jax.random.PRNGKey(1))[0]))
        l2 = float(np.asarray(step(ids, ids,
                                   rng_key=jax.random.PRNGKey(1))[0]))
        l3 = float(np.asarray(step(ids, ids,
                                   rng_key=jax.random.PRNGKey(2))[0]))
        assert l1 == l2
        assert l1 != l3

    def test_hybrid_dp_pp(self):
        m = _model()
        mesh = dist.make_mesh({"dp": 2, "pp": 4})
        step, _ = build_gpt_1f1b_step(m, mesh, axis_dp="dp")
        ids = _batches(4, 2, 8, m.config.vocab_size)
        loss, (gP, gF, gL) = step(ids, ids)
        assert np.isfinite(float(np.asarray(loss)))
        assert np.isfinite(np.asarray(gP[0]).sum())


class TestGPT1F1BDropoutReplay:
    """Train-mode dropout through the fused 1F1B pipeline: the recompute
    backward replays the forward's masks from threaded threefry keys
    (reference semantics: fleet/utils/recompute.py:63 RNG-state replay).
    Parity target: an eager tape run drawing masks with the IDENTICAL
    per-(microbatch, stage, layer) key schedule."""

    @pytest.mark.slow  # PR 21, ~11 s eager replica: dropout determinism per key stays in tier-1
    def test_train_dropout_loss_and_grad_parity(self):
        import jax
        from paddle_tpu.core import random as core_random

        paddle.seed(5)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=16,
                        hidden_dropout=0.1, attention_dropout=0.1)
        m = GPTForCausalLM(cfg)
        m.train()
        pp, M, mb, T = 4, 4, 2, 8
        per = cfg.num_layers // pp
        mesh = dist.make_mesh({"pp": pp})
        step, (stacked, first_p, last_p, leaf_names) = build_gpt_1f1b_step(
            m, mesh)
        ids = _batches(M, mb, T, cfg.vocab_size)

        base = jax.random.PRNGKey(123)
        loss, (gP, gF, gL) = step(ids, ids, rng_key=base)
        loss_pp = float(np.asarray(loss))

        # eager replica with the pipeline's exact key derivation
        keys = jax.random.split(base, M)
        p = cfg.hidden_dropout
        losses = []
        for i in range(M):
            k0 = jax.random.fold_in(keys[i], 0)
            x = m.gpt.wte(Tensor(ids[i]))
            pos = Tensor(np.arange(T, dtype=np.int32))
            x = x + m.gpt.wpe(pos)
            with core_random.scoped_key(jax.random.fold_in(k0, 997)):
                x = m.gpt.drop(x)  # same impl + key as the pipeline
            h = x
            for s in range(pp):
                ks = jax.random.fold_in(keys[i], s)
                for j in range(per):
                    with core_random.scoped_key(jax.random.fold_in(ks, j)):
                        h = m.gpt.blocks[s * per + j](h)
            norm = m.gpt.ln_f(h)
            import paddle_tpu.ops as _ops
            logits = _ops.matmul(norm, m.gpt.wte.weight, transpose_y=True)
            l = m.loss(logits, Tensor(ids[i])) / M
            l.backward()
            losses.append(float(np.asarray(l._value)) * M)
        loss_ref = float(np.mean(losses))
        np.testing.assert_allclose(loss_pp, loss_ref, rtol=1e-4)

        qkv_idx = leaf_names.index("qkv.weight")
        for s in range(pp):
            for j in range(per):
                blk = m.gpt.blocks[s * per + j]
                np.testing.assert_allclose(
                    np.asarray(gP[qkv_idx][s, j]),
                    np.asarray(blk.qkv.weight._grad), rtol=2e-3, atol=1e-5)
        # tied embedding grad: first (lookup scatter) + last (head matmul)
        tied = np.asarray(gF[0]) + np.asarray(gL[2])
        np.testing.assert_allclose(tied, np.asarray(m.gpt.wte.weight._grad),
                                   rtol=2e-3, atol=1e-5)
