"""Transformer LM family (mxtpu/models/transformer.py): shape contract,
causality, convergence, and data-parallel training over a mesh.

The reference era has no transformer (its sequence baseline is
example/rnn/lstm_bucketing.py); this family is the long-context flagship —
attention is the flash kernels (forward and backward) and the same blocks
drive the ring/ulysses sequence-parallel paths (tests/test_parallel.py)."""
import math

import numpy as np
import pytest

import mxtpu as mx


def _lm(vocab=50, seq=16, layers=2, heads=2, d=32):
    return mx.models.get_transformer_lm(vocab_size=vocab, seq_len=seq,
                                        num_layers=layers, num_heads=heads,
                                        d_model=d)


def _bind(net, batch=4, seq=16):
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[("data", (batch, seq))],
             label_shapes=[("softmax_label", (batch * seq,))])
    mod.init_params(mx.initializer.Xavier(), force_init=True)
    return mod


def test_shapes_and_params():
    net = _lm()
    args, outs, _ = net.infer_shape(data=(4, 16), softmax_label=(64,))
    assert outs == [(64, 50)]
    names = net.list_arguments()
    assert "tok_emb_weight" in names and "pos_emb" in names
    assert "l0_q_weight" in names and "l1_ff2_bias" in names


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note, PR 7):
# heaviest non-gate tests run in the slow tier (-m slow) so the
# 870s dots-in-window metric keeps measuring the whole fast tier
def test_causality():
    """Changing token t must not affect logits at positions < t."""
    net = _lm(layers=1)
    mod = _bind(net, batch=1)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 50, (1, 16)).astype("float32")
    lab = np.zeros((16,), "float32")

    def logits(t):
        db = mx.io.DataBatch(data=[mx.nd.array(t)],
                             label=[mx.nd.array(lab)])
        mod.forward(db, is_train=False)
        return mod.get_outputs()[0].asnumpy()

    base = logits(toks)
    toks2 = toks.copy()
    toks2[0, 10] = (toks2[0, 10] + 7) % 50
    pert = logits(toks2)
    # positions 0..9 identical, position >= 10 changed
    np.testing.assert_allclose(base[:10], pert[:10], rtol=1e-5, atol=1e-6)
    assert np.abs(base[10:] - pert[10:]).max() > 1e-4


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note, PR 7):
# heaviest non-gate tests run in the slow tier (-m slow) so the
# 870s dots-in-window metric keeps measuring the whole fast tier
def test_next_token_task_converges():
    net = _lm()
    mod = _bind(net)
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    toks = (np.arange(64).reshape(4, 16) % 50).astype("float32")
    lab = ((toks.reshape(-1) + 1) % 50).astype("float32")
    db = mx.io.DataBatch(data=[mx.nd.array(toks)], label=[mx.nd.array(lab)])
    for _ in range(60):
        mod.forward_backward(db)
        mod.update()
    out = mod.get_outputs()[0].asnumpy()
    nll = -np.log(out[np.arange(64), lab.astype(int)] + 1e-9).mean()
    assert nll < 1.0, "nll %.3f vs uniform %.3f" % (nll, math.log(50))


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note, PR 7):
# heaviest non-gate tests run in the slow tier (-m slow) so the
# 870s dots-in-window metric keeps measuring the whole fast tier
def test_data_parallel_mesh_training():
    """The same symbol trains through the fused GSPMD trainer over the
    8-device CPU mesh (batch sharded, params replicated)."""
    import jax

    from mxtpu.parallel import make_mesh
    from mxtpu.parallel.dp import DataParallelTrainer

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual multi-device mesh")
    mesh = make_mesh(shape=(4,))
    net = _lm(layers=1)
    batch = 8
    tr = DataParallelTrainer(
        net, mesh=mesh, optimizer="adam",
        optimizer_params={"learning_rate": 0.01,
                          "rescale_grad": 1.0 / (batch * 16)})
    tr.init({"data": (batch, 16), "softmax_label": (batch * 16,)})
    rng = np.random.RandomState(0)
    toks = (rng.randint(0, 50, (batch, 16))).astype("float32")
    lab = ((toks.reshape(-1) + 1) % 50).astype("float32")
    losses = []
    for _ in range(25):
        outs = tr.step({"data": toks, "softmax_label": lab})
        out = np.asarray(outs[0])
        nll = -np.log(out[np.arange(batch * 16), lab.astype(int)]
                      + 1e-9).mean()
        losses.append(nll)
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note, PR 7):
# heaviest non-gate tests run in the slow tier (-m slow) so the
# 870s dots-in-window metric keeps measuring the whole fast tier
def test_bucketing_shares_transformer_params():
    """BucketingModule over transformer symbols of different sequence
    lengths shares ONE parameter set (pos_emb sized by max_len, sliced
    per bucket) — the transformer analogue of the LSTM bucketing LM."""
    buckets = [8, 16]
    max_len = max(buckets)
    vocab = 30

    def gen(key):
        net = mx.models.get_transformer_lm(
            vocab_size=vocab, seq_len=key, num_layers=1, num_heads=2,
            d_model=16, max_len=max_len)
        return net, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(gen, default_bucket_key=max_len)
    rng = np.random.RandomState(0)

    def batch(T):
        toks = (rng.randint(0, vocab, (4, T))).astype("float32")
        lab = ((toks.reshape(-1) + 1) % vocab).astype("float32")
        return mx.io.DataBatch(
            data=[mx.nd.array(toks)], label=[mx.nd.array(lab)],
            bucket_key=T, provide_data=[("data", (4, T))],
            provide_label=[("softmax_label", (4 * T,))])

    mod.bind(data_shapes=[("data", (4, max_len))],
             label_shapes=[("softmax_label", (4 * max_len,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    losses = {8: [], 16: []}
    for i in range(30):
        T = buckets[i % 2]
        db = batch(T)
        mod.forward_backward(db)
        mod.update()
        out = mod.get_outputs()[0].asnumpy()
        lab = db.label[0].asnumpy().astype(int)
        losses[T].append(-np.log(out[np.arange(len(lab)), lab] + 1e-9)
                         .mean())
    # both buckets train through the SHARED weights
    assert losses[8][-1] < losses[8][0] * 0.7, losses[8]
    assert losses[16][-1] < losses[16][0] * 0.7, losses[16]
    arg_params, _ = mod.get_params()
    assert arg_params["pos_emb"].shape == (1, max_len, 16)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note, PR 7):
# heaviest non-gate tests run in the slow tier (-m slow) so the
# 870s dots-in-window metric keeps measuring the whole fast tier
def test_bf16_lm_trains():
    """dtype='bfloat16' variant (MXU-tiled matmuls, f32 softmax head):
    the LM still learns a deterministic-next-token stream — guards the
    cast placement (ids stay f32, logits back to f32) numerically."""
    from mxtpu.models import transformer

    rng = np.random.RandomState(3)
    vocab, T, batch = 24, 16, 8
    net = transformer.get_symbol(vocab, T, num_layers=2, num_heads=2,
                                 d_model=32, dtype="bfloat16")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", (batch, T))],
             label_shapes=[mx.io.DataDesc("softmax_label", (batch * T,))])
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01,
                                         "rescale_grad": 1.0 / batch})
    # deterministic cyclic stream: next token = (t + 1) % vocab
    nlls = []
    for step in range(60):
        starts = rng.randint(0, vocab, (batch, 1))
        toks = (starts + np.arange(T)) % vocab
        lab = ((toks + 1) % vocab).reshape(-1)
        b = mx.io.DataBatch(
            data=[mx.nd.array(toks.astype("float32"))],
            label=[mx.nd.array(lab.astype("float32"))])
        mod.forward(b, is_train=True)
        out = mod.get_outputs()[0].asnumpy()
        nll = -np.log(out[np.arange(batch * T), lab.astype(int)]
                      + 1e-9).mean()
        nlls.append(nll)
        mod.backward()
        mod.update()
    assert nlls[-1] < 0.3, "bf16 LM did not learn: %.3f" % nlls[-1]
    assert nlls[-1] < nlls[0] / 3
