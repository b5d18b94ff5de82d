"""QAT/PTQ quantization + ASP N:M sparsity.

Mirrors reference tests: slim/tests/test_imperative_qat.py,
test_post_training_quantization_*.py, asp/test_asp_pruning_1d.py,
asp/test_asp_optimize.py.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, quantization, sparsity
from paddle_tpu.quantization import (
    ImperativeQuantAware, PTQ, QuantizedLinear, fake_quant,
)


def test_fake_quant_forward_levels():
    x = paddle.to_tensor(np.linspace(-1, 1, 11).astype(np.float32))
    q = np.asarray(fake_quant(x, scale=1.0, bits=8).numpy())
    # quantized to the 127-level grid
    np.testing.assert_allclose(q * 127, np.round(q * 127), atol=1e-4)
    np.testing.assert_allclose(q, np.asarray(x.numpy()), atol=1.0 / 127)


def test_fake_quant_ste_gradient():
    x = paddle.to_tensor(np.array([0.3, 2.0, -0.5], np.float32))
    x.stop_gradient = False
    y = fake_quant(x, scale=1.0, bits=8)
    y.sum().backward()
    g = np.asarray(x.grad.numpy())
    # STE: grad 1 inside [-scale, scale], 0 outside
    np.testing.assert_allclose(g, [1.0, 0.0, 1.0])


def test_imperative_qat_swaps_layers():
    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, 8)
            self.inner = nn.Sequential(nn.Linear(8, 4), nn.ReLU())
            self.conv = nn.Conv2D(1, 2, 3)

        def forward(self, x):
            return self.inner(self.fc1(x))

    m = M()
    ImperativeQuantAware().quantize(m)
    assert isinstance(m.fc1, QuantizedLinear)
    assert isinstance(m.inner[0], QuantizedLinear)
    assert type(m.conv).__name__ == "QuantizedConv2D"
    x = paddle.to_tensor(np.random.rand(2, 8).astype(np.float32))
    out = m(x)
    assert tuple(out.shape) == (2, 4)


def test_qat_output_close_to_float():
    paddle.seed(0)
    lin = nn.Linear(16, 16)
    x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
    ref = np.asarray(lin(x).numpy())
    qlin = QuantizedLinear(lin)
    got = np.asarray(qlin(x).numpy())
    # int8 simulation error is small relative to activation magnitude
    assert np.abs(got - ref).max() < 0.15 * np.abs(ref).max() + 0.05


def test_qat_trains():
    """QAT on a toy regression must still converge (grad flows through STE)."""
    paddle.seed(0)
    np.random.seed(0)
    lin = nn.Linear(4, 1)
    ImperativeQuantAware().quantize(model := nn.Sequential(lin))
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=0.05)
    w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    first = last = None
    for i in range(60):
        xb = np.random.randn(32, 4).astype(np.float32)
        yb = xb @ w_true
        loss = paddle.nn.functional.mse_loss(
            model(paddle.to_tensor(xb)), paddle.to_tensor(yb))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if first is None:
            first = float(loss.numpy())
    last = float(loss.numpy())
    assert last < first * 0.1, (first, last)


def test_ptq_absmax_calibration():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))

    def loader():
        rng = np.random.RandomState(0)
        for _ in range(4):
            yield (paddle.to_tensor(rng.randn(16, 8).astype(np.float32)),)

    PTQ(algo="abs_max").quantize(model, loader())
    q0 = model[0]
    assert q0._frozen and q0._act_scale_initialized
    assert q0._act_scale > 0
    # frozen: scale stops moving
    s = q0._act_scale
    model(paddle.to_tensor(np.random.randn(4, 8).astype(np.float32) * 100))
    assert q0._act_scale == s


def test_ptq_percentile_calibration():
    model = nn.Sequential(nn.Linear(8, 4))

    def loader():
        rng = np.random.RandomState(1)
        for _ in range(4):
            yield (paddle.to_tensor(rng.randn(64, 8).astype(np.float32)),)

    PTQ(algo="percentile", percentile=0.99).quantize(model, loader())
    q = model[0]
    # 99th percentile of |N(0,1)| is ~2.58, well below abs max over 256 samples
    assert 2.0 < q._act_scale < 3.2


# ---------------- ASP ----------------

def test_create_mask_2_4():
    w = paddle.to_tensor(np.random.randn(8, 12).astype(np.float32))
    mask = sparsity.create_mask(w, n=2, m=4)
    assert sparsity.check_mask_1d(mask, 2, 4)
    assert mask.sum() == 8 * 12 // 2  # exactly half kept
    # kept entries are the largest-|.| of each group
    wv = np.asarray(w.numpy()).reshape(8, 3, 4)
    mv = mask.reshape(8, 3, 4)
    for r in range(8):
        for g in range(3):
            kept = set(np.where(mv[r, g] == 1)[0])
            top2 = set(np.argsort(-np.abs(wv[r, g]))[:2])
            assert kept == top2


def test_create_mask_nondivisible_cols():
    w = paddle.to_tensor(np.random.randn(4, 10).astype(np.float32))
    mask = sparsity.create_mask(w, n=2, m=4)
    assert mask.shape == (4, 10)
    assert sparsity.check_sparsity(mask, n=2, m=4)


def test_prune_model_and_density():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 16), nn.ReLU(), nn.Linear(16, 8))
    sparsity.prune_model(model, n=2, m=4)
    for _, p in model.named_parameters():
        if len(p.shape) >= 2:
            assert sparsity.check_mask_1d(p, 2, 4)
            assert abs(sparsity.calculate_density(p) - 0.5) < 1e-6


def test_asp_decorated_optimizer_keeps_masks():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8))
    opt = sparsity.decorate(
        paddle.optimizer.SGD(parameters=model.parameters(),
                             learning_rate=0.1))
    sparsity.prune_model(model, n=2, m=4)
    zero_positions = np.asarray(model[0].weight.numpy()) == 0
    for _ in range(3):
        x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
        loss = model(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    w = np.asarray(model[0].weight.numpy())
    # the pruned slots stay zero through optimizer updates
    assert (w[zero_positions] == 0).all()
    assert sparsity.check_mask_1d(w, 2, 4)


def test_channel_wise_weight_scales_beat_per_tensor():
    """channel_wise_abs_max: per-output-channel scales quantize a weight
    with wildly different column magnitudes far better than one tensor
    scale (reference: fake_quantize_op.cc FakeChannelWiseQuantizeAbsMax)."""
    from paddle_tpu.quantization import ImperativeQuantAware

    rng_l = np.random.RandomState(0)
    w = rng_l.randn(8, 4).astype(np.float32)
    w[:, 0] *= 100.0  # one loud column drowns the per-tensor scale
    x = rng_l.rand(5, 8).astype(np.float32)

    def build(channel):
        m = paddle.nn.Linear(8, 4)
        m.weight.set_value(w)
        m.bias.set_value(np.zeros(4, np.float32))
        qt = "channel_wise_abs_max" if channel else "abs_max"
        ImperativeQuantAware(weight_quantize_type=qt).quantize(
            nn_wrap := paddle.nn.Sequential(m))
        return nn_wrap

    ref = x @ w
    err_t = np.abs(np.asarray(build(False)(paddle.to_tensor(x)).numpy())
                   - ref)[:, 1:].mean()
    err_c = np.abs(np.asarray(build(True)(paddle.to_tensor(x)).numpy())
                   - ref)[:, 1:].mean()
    assert err_c < err_t / 4


def test_quantized_embedding_swap_and_forward():
    from paddle_tpu.quantization import ImperativeQuantAware, \
        QuantizedEmbedding

    m = paddle.nn.Sequential(paddle.nn.Embedding(16, 8))
    ImperativeQuantAware(
        quantizable_layer_type=("Embedding",)).quantize(m)
    assert isinstance(m[0], QuantizedEmbedding)
    ids = paddle.to_tensor(np.array([1, 5, 9], np.int64))
    out = m(ids)
    assert out.shape == [3, 8]


def test_output_scales_and_sidecar(tmp_path):
    from paddle_tpu.quantization import (ImperativeQuantAware,
                                         load_quant_scales)
    from paddle_tpu.jit.to_static import InputSpec

    model = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
                                 paddle.nn.Linear(8, 2))
    q = ImperativeQuantAware()
    q.quantize(model)
    for _ in range(3):
        model(paddle.to_tensor(np.random.RandomState(1)
                               .rand(2, 4).astype(np.float32)))
    prefix = str(tmp_path / "qmodel")
    q.save_quantized_model(model, prefix,
                           input_spec=[InputSpec([None, 4], "float32")])
    scales = load_quant_scales(prefix)
    assert len(scales) == 2  # two quantized Linears
    for rec in scales.values():
        assert rec["act_scale"] > 0 and rec["out_scale"] > 0
        assert rec["weight_bits"] == 8


@pytest.mark.slow  # ~20 s resnet PTQ + artifact round-trip; quant op
# semantics stay tier-1-covered by the per-op cases in this file
def test_ptq_resnet_serving_accuracy_delta(tmp_path):
    """The bar: PTQ a ResNet, serve the saved artifact through
    the Predictor in-process, assert the quantized predictions track the
    float model (top-1 agreement)."""
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.jit.to_static import InputSpec
    from paddle_tpu.quantization import PTQ, ImperativeQuantAware
    from paddle_tpu.vision.models import resnet18

    paddle.seed(7)
    rng_l = np.random.RandomState(3)
    imgs = rng_l.rand(8, 3, 32, 32).astype(np.float32)

    float_model = resnet18(num_classes=10)
    float_model.eval()
    float_logits = np.asarray(
        float_model(paddle.to_tensor(imgs)).numpy())

    calib = [(paddle.to_tensor(imgs[i:i + 2]),) for i in range(0, 8, 2)]
    qmodel = PTQ(algo="abs_max").quantize(float_model, calib)
    prefix = str(tmp_path / "resnet_q")
    ImperativeQuantAware.save_quantized_model(
        qmodel, prefix,
        input_spec=[InputSpec([None, 3, 32, 32], "float32")])
    assert os.path.exists(prefix + ".quant.json")

    pred = create_predictor(Config(prefix + ".pdmodel",
                                   prefix + ".pdiparams"))
    name = pred.get_input_names()[0]
    pred.get_input_handle(name).copy_from_cpu(imgs)
    pred.run()
    served = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()

    agree = (served.argmax(-1) == float_logits.argmax(-1)).mean()
    assert agree >= 0.75, agree
    # logits deviation bounded (8-bit fake-quant on a float backbone)
    rel = np.abs(served - float_logits).mean() / (
        np.abs(float_logits).mean() + 1e-6)
    assert rel < 0.5, rel
