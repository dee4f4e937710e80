"""2.0 nn/optimizer/jit API tests (dygraph mode, CPU)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.jit as jit
from paddle_tpu.nn import functional as F


@pytest.fixture(autouse=True)
def _seed():
    paddle.seed(42)


def _class_data(rng, W, n=128):
    x = rng.randn(n, W.shape[0]).astype(np.float32)
    y = (x @ W).argmax(-1).astype(np.int64)
    return x, y


def test_sequential_train_eager():
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 3))
    opt = paddle.optimizer.Adam(learning_rate=5e-3,
                                parameters=model.parameters())
    lossfn = nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    W = rng.randn(8, 3).astype(np.float32)
    losses = []
    for _ in range(60):
        x, y = _class_data(rng, W)
        loss = lossfn(model(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0] * 0.5


def test_jit_train_step_matches_eager():
    """Same seed -> jit step and eager step produce identical params."""
    def build():
        paddle.seed(7)
        m = nn.Sequential(nn.Linear(6, 16), nn.Tanh(), nn.Linear(16, 2))
        o = paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=m.parameters())
        return m, o

    lossfn = nn.MSELoss()
    rng = np.random.RandomState(1)
    batches = [(rng.randn(32, 6).astype(np.float32),
                rng.randn(32, 2).astype(np.float32)) for _ in range(5)]

    m1, o1 = build()
    for x, y in batches:
        loss = lossfn(m1(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        o1.step()
        o1.clear_grad()

    m2, o2 = build()

    @jit.to_static(layers=[m2], optimizers=[o2])
    def step(x, y):
        loss = lossfn(m2(x), y)
        loss.backward()
        o2.step()
        o2.clear_grad()
        return loss

    for x, y in batches:
        step(x, y)

    for p1, p2 in zip(m1.parameters(), m2.parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy(), atol=1e-5)


@pytest.mark.parametrize("optimizer", ["AdamW", "Adam", "Momentum"])
def test_a_compiled_train_step_is_one_program_and_matches_eager(optimizer):
    """``to_static`` makes its optimizers' accumulators before the first
    trace (PR 60): with the grads kept inside the step the state has one
    structure from the first call on and the site builds ONE program (to
    PR 59: one for the call that made the moments, one for every call
    after it), and the parameters are the eager steps', a layer that gets
    no gradient and so no update among them."""
    from paddle_tpu import observability
    from paddle_tpu.observability import compile_tracker as ct

    def build():
        paddle.seed(11)
        m = nn.Sequential(nn.Linear(6, 16), nn.Tanh(), nn.Linear(16, 2))
        idle = nn.Linear(3, 3)          # in the state, never in the loss
        o = getattr(paddle.optimizer, optimizer)(
            learning_rate=0.05, parameters=m.parameters() + idle.parameters())
        return m, idle, o

    lossfn = nn.MSELoss()
    rng = np.random.RandomState(2)
    batches = [(rng.randn(16, 6).astype(np.float32),
                rng.randn(16, 2).astype(np.float32)) for _ in range(4)]
    m1, idle1, o1 = build()
    for x, y in batches:
        loss = lossfn(m1(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        o1.step()
        o1.clear_grad()

    m2, idle2, o2 = build()

    def one_program_step(x, y):
        loss = lossfn(m2(x), y)
        loss.backward()
        o2.step()
        o2.clear_grad()
        return loss
    one_program_step.__name__ += "_" + optimizer
    step = jit.to_static(one_program_step, layers=[m2, idle2],
                         optimizers=[o2], retain_grads=False)
    for x, y in batches:
        step(x, y)
    site = observability.compiles()[
        ct._qualname("to_static", {"py_fn": one_program_step.__name__})]
    assert site["count"] == 1 and site["programs"] == 1
    for p1, p2 in zip(m1.parameters() + idle1.parameters(),
                      m2.parameters() + idle2.parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy(), atol=1e-5)


def test_transformer_encoder_backward():
    enc = nn.TransformerEncoder(
        nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0), num_layers=2)
    x = paddle.to_tensor(np.random.randn(2, 5, 16).astype(np.float32))
    out = enc(x)
    assert out.shape == [2, 5, 16]
    out.mean().backward()
    assert all(p.grad is not None for p in enc.parameters())


def test_transformer_full():
    model = nn.Transformer(d_model=16, nhead=2, num_encoder_layers=2,
                           num_decoder_layers=2, dim_feedforward=32,
                           dropout=0.0)
    src = paddle.to_tensor(np.random.randn(2, 6, 16).astype(np.float32))
    tgt = paddle.to_tensor(np.random.randn(2, 4, 16).astype(np.float32))
    tgt_mask = nn.Transformer.generate_square_subsequent_mask(4)
    out = model(src, tgt, tgt_mask=tgt_mask)
    assert out.shape == [2, 4, 16]
    # layers are independently initialized (not weight-shared clones)
    l0 = model.encoder.layers[0].linear1.weight.numpy()
    l1 = model.encoder.layers[1].linear1.weight.numpy()
    assert not np.allclose(l0, l1)


def test_mha_causal_cache_decoding():
    """Incremental decoding with Cache == full forward with causal mask."""
    mha = nn.MultiHeadAttention(8, 2)
    mha.eval()
    x = paddle.to_tensor(np.random.randn(1, 4, 8).astype(np.float32))
    # full causal
    m = np.full((1, 1, 4, 4), np.finfo(np.float32).min, np.float32)
    m = np.triu(m, 1)
    full = mha(x, x, x, attn_mask=paddle.to_tensor(m))
    # incremental
    cache = mha.gen_cache(x[:, :1, :] * 0)
    cache = nn.MultiHeadAttention.Cache(cache.k, cache.v)
    outs = []
    for t in range(4):
        step_in = x[:, t:t + 1, :]
        o, cache = mha(step_in, step_in, step_in, None, cache)
        outs.append(o.numpy())
    inc = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(full.numpy(), inc, atol=1e-4)


def test_batch_norm_running_stats():
    bn = nn.BatchNorm2D(3)
    x = paddle.to_tensor(
        (2.0 + np.random.randn(8, 3, 4, 4)).astype(np.float32))
    bn.train()
    bn(x)
    m1 = bn._mean.numpy().copy()
    assert not np.allclose(m1, 0.0)  # stats updated
    bn.eval()
    y = bn(x)
    np.testing.assert_allclose(bn._mean.numpy(), m1)  # frozen in eval


def test_conv_pool_stack():
    net = nn.Sequential(
        nn.Conv2D(1, 4, 3, padding=1), nn.ReLU(), nn.MaxPool2D(2),
        nn.Conv2D(4, 8, 3, padding=1), nn.ReLU(),
        nn.AdaptiveAvgPool2D(1), nn.Flatten(), nn.Linear(8, 2))
    x = paddle.to_tensor(np.random.randn(2, 1, 8, 8).astype(np.float32))
    out = net(x)
    assert out.shape == [2, 2]
    out.sum().backward()
    assert all(p.grad is not None for p in net.parameters())


def test_optimizer_grad_clip_eager():
    from paddle_tpu.optimizer import GradientClipByGlobalNorm
    m = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=1.0,
                               parameters=m.parameters(),
                               grad_clip=GradientClipByGlobalNorm(0.1))
    x = paddle.to_tensor(100 * np.ones((2, 4), np.float32))
    m(x).sum().backward()
    before = [p.numpy().copy() for p in m.parameters()]
    opt.step()
    total = 0.0
    for p, b in zip(m.parameters(), before):
        total += np.sum((p.numpy() - b) ** 2)
    assert np.sqrt(total) <= 0.1 + 1e-5  # update bounded by clipped norm*lr


def test_amp_autocast_eager():
    from paddle_tpu.amp import auto_cast
    m = nn.Linear(8, 8, bias_attr=False)
    x = paddle.to_tensor(np.random.randn(2, 8).astype(np.float32))
    with auto_cast(level="O1"):
        y = m(x)
    # matmul ran in bf16 (white list)
    assert y.dtype == "bfloat16"
    y.astype("float32").mean().backward()
    assert m.weight.grad is not None
    assert m.weight.grad.dtype == "float32"  # master grads stay f32


def test_grad_scaler():
    from paddle_tpu.amp import GradScaler
    m = nn.Linear(4, 1)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())
    scaler = GradScaler(init_loss_scaling=128.0)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    loss = m(x).mean()
    scaled = scaler.scale(loss)
    scaled.backward()
    scaler.unscale_(opt)
    # after unscale, grads are the true grads:
    # dW_j = sum_i x_ij * (1/batch) = 2 * 0.5 = 1.0
    np.testing.assert_allclose(m.weight.grad.numpy(),
                               np.ones((4, 1)), atol=1e-5)


def test_save_load_state_dict(tmp_path):
    m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    path = str(tmp_path / "model.pdparams")
    paddle.save(m.state_dict(), path)
    m2 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    loaded = paddle.load(path)
    m2.set_state_dict(loaded)
    for (k1, p1), (k2, p2) in zip(m.state_dict().items(),
                                  m2.state_dict().items()):
        np.testing.assert_array_equal(np.asarray(p1.numpy()),
                                      np.asarray(p2.numpy()))


def test_tensor_api_surface():
    a = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
    assert paddle.sum(a).item() == 15.0
    assert paddle.mean(a).item() == 2.5
    assert paddle.argmax(a, axis=1).numpy().tolist() == [2, 2]
    b = paddle.concat([a, a], axis=0)
    assert b.shape == [4, 3]
    c = paddle.transpose(a, [1, 0])
    assert c.shape == [3, 2]
    v, i = paddle.topk(a, 2)
    assert v.shape == [2, 2]
    w = paddle.where(a > 2.0, a, paddle.zeros_like(a))
    assert float(w.numpy()[0, 0]) == 0.0
