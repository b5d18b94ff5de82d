"""End-to-end training (reference model: tests/book/ 'book' e2e suite +
test_mnist dygraph tests): LeNet must actually learn the synthetic MNIST."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.io import DataLoader
from paddle_tpu.vision.datasets import MNIST
from paddle_tpu.vision.models import LeNet


def test_lenet_learns():
    paddle.seed(1)
    train = MNIST(mode="train")
    loader = DataLoader(train, batch_size=64, shuffle=True, drop_last=True)
    model = LeNet()
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-3)

    @paddle.jit.to_static
    def step(x, y):
        logits = model(x)
        loss = nn.functional.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss, logits

    first_loss = None
    last_acc = 0.0
    for epoch in range(2):
        for i, (x, y) in enumerate(loader):
            loss, logits = step(x, y)
            if first_loss is None:
                first_loss = float(loss.numpy())
            if i >= 20:
                break
        pred = logits.numpy().argmax(-1)
        last_acc = (pred == y.numpy().reshape(-1)).mean()
    assert float(loss.numpy()) < first_loss
    assert last_acc > 0.5, f"accuracy {last_acc} too low: model not learning"


def test_hapi_model_fit():
    paddle.seed(2)
    train = MNIST(mode="train")
    model = paddle.Model(LeNet())
    model.prepare(
        optimizer=paddle.optimizer.Adam(parameters=model.parameters()),
        loss=nn.CrossEntropyLoss(),
        metrics=paddle.metric.Accuracy())
    hist = model.fit(train, batch_size=128, epochs=1, verbose=0)
    res = model.evaluate(train, batch_size=256)
    assert "acc" in res
    assert res["acc"] > 0.3


def test_hapi_predict_save_load(tmp_path):
    model = paddle.Model(LeNet())
    model.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.1, parameters=model.parameters()),
        loss=nn.CrossEntropyLoss())
    ds = MNIST(mode="test")
    out = model.predict(ds, batch_size=64)
    assert out[0][0].shape[-1] == 10
    model.save(str(tmp_path / "ckpt"))
    model2 = paddle.Model(LeNet())
    model2.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.1, parameters=model2.parameters()),
        loss=nn.CrossEntropyLoss())
    model2.load(str(tmp_path / "ckpt"))
    for (k1, v1), (k2, v2) in zip(sorted(model.network.state_dict().items()),
                                  sorted(model2.network.state_dict().items())):
        np.testing.assert_allclose(v1.numpy(), v2.numpy())


@pytest.mark.slow  # PR 21, ~16 s: eager resnet18 fwd+bwd compiles op by op; LeNet e2e and the conv/bn layer tests keep tier-1 coverage
def test_resnet18_smoke():
    from paddle_tpu.vision.models import resnet18
    m = resnet18(num_classes=10)
    x = paddle.to_tensor(np.random.rand(2, 3, 32, 32).astype("float32"))
    out = m(x)
    assert out.shape == [2, 10]
    loss = out.sum()
    loss.backward()
    assert m.conv1.weight.grad is not None
