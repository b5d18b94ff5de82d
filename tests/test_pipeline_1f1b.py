"""1F1B pipeline parallelism (reference: section_worker.cc:148-175).

Asserts the two 1F1B contracts the reference schedule exists for:
loss/grad parity with sequential execution (incl. non-uniform embed/head
stages), and O(S) — not O(M) — activation liveness.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.parallel import spmd_pipeline_1f1b, ring_buffer_size

rng = np.random.RandomState(7)


def _pipeline_fn(mesh, first_fn=None):
    def run(stage_params, last_params, first_params, micro, labels):
        return jax.shard_map(
            lambda sp, lp, fp, x, y: spmd_pipeline_1f1b(
                _stage, _head_loss, sp, lp, x, y,
                first_fn=first_fn, first_params=fp, axis_name="pp"),
            mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stage_params),
                      P(), P(), P(None), P(None)),
            out_specs=(P(), jax.tree_util.tree_map(lambda _: P("pp"),
                                                   stage_params), P(), P()),
        )(stage_params, last_params, first_params, micro, labels)
    # jitted: an eager shard_map dispatches (and compiles) op by op
    # across the mesh, ~10x the wall time of the one program
    return jax.jit(run)


def _stage(params, h):
    w, b = params
    return jnp.tanh(h @ w + b)


def _head_loss(head_w, h, y):
    logits = h @ head_w
    return jnp.mean((logits - y) ** 2)


class TestRingBuffer:
    def test_liveness_is_O_S_not_O_M(self):
        # GPipe stores M activations; 1F1B must be bounded by the stage count
        assert ring_buffer_size(n_stages=2, n_micro=64) == 3
        assert ring_buffer_size(n_stages=4, n_micro=64) == 7
        assert ring_buffer_size(n_stages=4, n_micro=128) == 7  # M-independent
        assert ring_buffer_size(n_stages=4, n_micro=2) == 2  # small M capped


class TestParity:
    def test_uniform_stages_loss_and_grads(self):
        mesh = dist.make_mesh({"pp": 4})
        S, M, mb, dim = 4, 8, 2, 16
        w = (rng.randn(S, dim, dim) * 0.2).astype(np.float32)
        b = (rng.randn(S, dim) * 0.1).astype(np.float32)
        head = (rng.randn(dim, dim) * 0.2).astype(np.float32)
        x = rng.randn(M, mb, dim).astype(np.float32)
        y = rng.randn(M, mb, dim).astype(np.float32)

        loss, gP, gF, gL = _pipeline_fn(mesh)((w, b), head,
                                              jnp.zeros((), jnp.float32),
                                              x, y)

        def ref_loss(params, head_w):
            w_, b_ = params
            losses = []
            for m in range(M):
                h = x[m]
                for s in range(S):
                    h = jnp.tanh(h @ w_[s] + b_[s])
                losses.append(_head_loss(head_w, h, y[m]))
            return jnp.mean(jnp.stack(losses))

        ref_v, (g_wb, g_head) = jax.value_and_grad(
            ref_loss, argnums=(0, 1))((w, b), head)
        np.testing.assert_allclose(float(loss), float(ref_v), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gP[0]), np.asarray(g_wb[0]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gP[1]), np.asarray(g_wb[1]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gL), np.asarray(g_head),
                                   rtol=1e-4, atol=1e-5)

    def test_nonuniform_embed_and_head_stages(self):
        """The lifted restriction: stage 0 embeds int token ids (raw input
        shape ≠ hidden shape), the last stage computes the loss."""
        mesh = dist.make_mesh({"pp": 4})
        S, M, mb, T, V, dim = 4, 8, 2, 6, 32, 16
        emb = (rng.randn(V, dim) * 0.1).astype(np.float32)
        w = (rng.randn(S, dim, dim) * 0.2).astype(np.float32)
        b = (rng.randn(S, dim) * 0.1).astype(np.float32)
        head = (rng.randn(dim, dim) * 0.2).astype(np.float32)
        ids = rng.randint(0, V, size=(M, mb, T)).astype(np.int32)
        y = rng.randn(M, mb, T, dim).astype(np.float32)

        def embed(e, token_ids):
            return e[token_ids]

        loss, gP, gE, gL = _pipeline_fn(mesh, first_fn=embed)(
            (w, b), head, emb, ids, y)

        def ref_loss(params, head_w, e):
            w_, b_ = params
            losses = []
            for m in range(M):
                h = e[ids[m]]
                for s in range(S):
                    h = jnp.tanh(h @ w_[s] + b_[s])
                losses.append(_head_loss(head_w, h, y[m]))
            return jnp.mean(jnp.stack(losses))

        ref_v, (g_wb, g_head, g_emb) = jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2))((w, b), head, emb)
        np.testing.assert_allclose(float(loss), float(ref_v), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gP[0]), np.asarray(g_wb[0]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gE), np.asarray(g_emb),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gL), np.asarray(g_head),
                                   rtol=1e-4, atol=1e-5)

    def test_nan_safe_loss_in_warmup(self):
        """Out-of-window backward runs on garbage (zero) activations; a
        log-based loss must not poison gradients via 0*NaN."""
        mesh = dist.make_mesh({"pp": 2})
        S, M, mb, dim = 2, 4, 2, 8
        w = (rng.randn(S, dim, dim) * 0.2).astype(np.float32)
        b = np.zeros((S, dim), np.float32)
        head = (rng.randn(dim, dim) * 0.2).astype(np.float32)
        x = np.abs(rng.randn(M, mb, dim)).astype(np.float32) + 0.5
        y = rng.randint(0, dim, size=(M, mb)).astype(np.int32)

        def log_loss(head_w, h, labels):
            logits = h @ head_w
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)
            return -jnp.mean(picked)

        def run(sp, lp, fp, xx, yy):
            return spmd_pipeline_1f1b(_stage, log_loss, sp, lp, xx, yy,
                                      first_params=fp, axis_name="pp")

        loss, gP, _, gL = jax.jit(jax.shard_map(
            run, mesh=mesh,
            in_specs=((P("pp"), P("pp")), P(), P(), P(None), P(None)),
            out_specs=(P(), (P("pp"), P("pp")), P(), P()),
        ))((w, b), head, jnp.zeros((), jnp.float32), x, y)
        assert np.isfinite(float(loss))
        assert np.isfinite(np.asarray(gP[0])).all()
        assert np.isfinite(np.asarray(gL)).all()

    def test_more_microbatches_than_buffer(self):
        """M >> 2S-1: the ring reuses slots; results must stay exact."""
        mesh = dist.make_mesh({"pp": 2})
        S, M, mb, dim = 2, 12, 2, 8
        w = (rng.randn(S, dim, dim) * 0.2).astype(np.float32)
        b = np.zeros((S, dim), np.float32)
        head = (rng.randn(dim, dim) * 0.2).astype(np.float32)
        x = rng.randn(M, mb, dim).astype(np.float32)
        y = rng.randn(M, mb, dim).astype(np.float32)
        assert ring_buffer_size(S, M) == 3 < M

        loss, gP, _, gL = _pipeline_fn(mesh)((w, b), head,
                                             jnp.zeros((), jnp.float32), x, y)

        def ref_loss(params, head_w):
            w_, b_ = params
            losses = []
            for m in range(M):
                h = x[m]
                for s in range(S):
                    h = jnp.tanh(h @ w_[s] + b_[s])
                losses.append(_head_loss(head_w, h, y[m]))
            return jnp.mean(jnp.stack(losses))

        ref_v, (g_wb, g_head) = jax.value_and_grad(
            ref_loss, argnums=(0, 1))((w, b), head)
        np.testing.assert_allclose(float(loss), float(ref_v), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gP[0]), np.asarray(g_wb[0]),
                                   rtol=1e-4, atol=1e-5)
