"""Convergence gates driven through the EXAMPLE ENTRY POINTS themselves
(VERDICT r1 weak #7): the baseline configs must train, not just their
re-implementations in test files.

Model: reference tests/python/train/test_mlp.py:82 (accuracy >0.95 gate),
example/rnn/lstm_bucketing.py (perplexity falls), example/ssd/evaluate.py
(mAP improves with training).
"""
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _example(*parts):
    path = os.path.join(_ROOT, "examples", *parts)
    sys.path.insert(0, os.path.dirname(path))
    return path


@pytest.mark.parametrize("network,epochs", [
    ("mlp", 12),
    pytest.param("lenet", 5, marks=pytest.mark.slow),  # tier-1 time
    # budget: the conv path is covered by the quicker gates; the full
    # 5-epoch lenet convergence gate runs in the slow tier
])
def test_train_mnist_gate(tmp_path, network, epochs):
    """LeNet/MLP on deterministic idx-format glyph MNIST through
    examples/image_classification/train_mnist.py must clear 0.95
    validation accuracy (the reference's MNIST gate)."""
    _example("image_classification", "train_mnist.py")
    import train_mnist
    acc = train_mnist.main([
        "--data-dir", str(tmp_path / "mnist"),
        "--network", network, "--num-epochs", str(epochs),
        "--lr", "0.05", "--batch-size", "64"])
    assert acc > 0.95, "%s reached only %.3f" % (network, acc)


def test_lstm_bucketing_gate():
    """BucketingModule LSTM LM through examples/rnn/lstm_bucketing.py:
    validation perplexity must fall clearly below its starting point
    (synthetic next-token corpus; random baseline ppl ~58).

    Gate re-derived 2026-08-04 (un-quarantining the PR-2 red): under
    jax 0.4.37 this config's loss plateaus for ~6 epochs before the
    phase transition — the old 6-epoch budget measured the plateau, not
    convergence (ratio stalled at 0.85-0.88). At 10 epochs the seeded
    trajectory breaks through decisively (ratios vs epoch-1:
    [1.0, .99, 1.01, 1.04, .99, .99, .72, .67, .62, .65]), so the 0.8
    bar is kept AS-IS and only the training budget moved to where
    current-jax convergence actually happens. Divergence still fails
    this gate: lr sweeps at 0.05/0.1 blow up past ratio 1.3."""
    _example("rnn", "lstm_bucketing.py")
    import mxtpu as mx
    import lstm_bucketing
    mx.random.seed(7)  # deterministic init regardless of suite order
    np.random.seed(7)  # NDArrayIter shuffle draws from numpy's global RNG
    ppl = lstm_bucketing.main([
        "--num-epochs", "10", "--num-hidden", "64", "--num-embed", "32"])
    assert len(ppl) == 10
    assert min(ppl[2:]) < ppl[0] * 0.8, \
        "perplexity did not fall: %s" % (ppl,)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_transformer_lm_gate():
    """Transformer LM through examples/transformer_lm/train_lm.py:
    perplexity falls AND the trained-weights seq-parallel ring-attention
    check agrees with single-device flash attention."""
    _example("transformer_lm", "train_lm.py")
    import mxtpu as mx
    import train_lm
    mx.random.seed(7)  # deterministic init regardless of suite order
    np.random.seed(7)  # NDArrayIter shuffle draws from numpy's global RNG
    ppl = train_lm.main(["--epochs", "2", "--seq-len", "32",
                         "--d-model", "64", "--num-heads", "4",
                         "--seq-parallel"])
    assert len(ppl) == 2
    assert ppl[1] < ppl[0] * 0.8, "perplexity did not fall: %s" % (ppl,)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_ssd_gate(tmp_path):
    """SSD through examples/ssd/train.py + evaluate.py: mAP on painted
    synthetic boxes must improve materially over the untrained net."""
    _example("ssd", "train.py")
    import mxtpu as mx
    import train as ssd_train
    import evaluate as ssd_eval
    prefix = str(tmp_path / "ssd")
    common = ["--data-shape", "64", "--num-classes", "3",
              "--num-scales", "3", "--batch-size", "8",
              "--network", "tiny"]
    map_untrained = ssd_eval.main(common + ["--num-batches", "2"])
    # seed immediately before training so the init draw is deterministic
    # regardless of suite order or the eval above
    mx.random.seed(2)
    np.random.seed(2)  # NDArrayIter shuffle draws from numpy's global RNG
    _mod, metrics = ssd_train.main(common + [
        "--num-batches", "8", "--num-epochs", "12", "--lr", "0.05",
        "--prefix", prefix])
    assert dict(metrics)["CrossEntropy"] < 1.2, metrics
    map_trained = ssd_eval.main(common + [
        "--num-batches", "2", "--prefix", prefix, "--epoch", "12"])
    assert map_trained > max(map_untrained, 0.05), \
        "mAP did not improve: %.4f -> %.4f" % (map_untrained, map_trained)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_train_imagenet_on_packed_rec(tmp_path):
    """config-2 flow end to end on real (synthetic-JPEG) recordio data:
    pack a .rec, run examples/image_classification/train_imagenet.py on a
    tiny resnet, get a steady-state throughput measurement (VERDICT r1
    weak #5: steady-state step time with real data)."""
    _example("image_classification", "train_imagenet.py")
    import train_imagenet
    from mxtpu.test_utils import make_rec

    rec = make_rec(str(tmp_path / "synth.rec"), 96, edge=40)
    speed = train_imagenet.main([
        "--data-train", rec, "--num-layers", "18",
        "--image-shape", "3,32,32", "--num-classes", "10",
        "--batch-size", "16", "--num-epochs", "2", "--kv-store", "local",
        "--speedometer-period", "2"])
    assert speed > 0, "no steady-state throughput measured"


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_gluon_word_lm_gate():
    """Imperative Gluon LSTM LM through examples/gluon/word_language_model
    (parity: the reference's example/gluon/word_language_model): validation
    perplexity must fall on the synthetic Markov corpus."""
    _example("gluon", "word_language_model.py")
    import mxtpu as mx
    import word_language_model
    mx.random.seed(11)
    np.random.seed(11)  # NDArrayIter shuffle draws from numpy's global RNG
    ppl = word_language_model.main(["--epochs", "4", "--n-tokens", "8000",
                                    "--num-hidden", "48", "--lr", "2"])
    assert len(ppl) == 4
    assert ppl[-1] < ppl[0] * 0.5, "val ppl did not fall: %s" % (ppl,)


def test_gluon_super_resolution_gate():
    """ESPCN-style super resolution through examples/gluon/
    super_resolution.py (parity: the reference's gluon example): val PSNR
    must rise clearly above the untrained net's."""
    _example("gluon", "super_resolution.py")
    import mxtpu as mx
    import super_resolution
    mx.random.seed(3)
    np.random.seed(3)  # NDArrayIter shuffle draws from numpy's global RNG
    psnrs = super_resolution.main(["--epochs", "2"])
    assert psnrs[-1] > psnrs[0] + 3.0, \
        "PSNR did not improve enough: %s" % (psnrs,)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_gluon_dcgan_gate():
    """DCGAN through examples/gluon/dcgan.py (parity: the reference's
    example/gluon/dcgan.py): the Conv2DTranspose generator must at some
    point genuinely fool the discriminator (min fake-detection < 0.9,
    vs ~1.0 against an untrained generator)."""
    _example("gluon", "dcgan.py")
    import mxtpu as mx
    import dcgan
    mx.random.seed(5)
    np.random.seed(5)  # NDArrayIter shuffle draws from numpy's global RNG
    acc0, min_acc = dcgan.main(["--epochs", "4"])
    assert min_acc < 0.9, \
        "generator never fooled the discriminator: first=%s min=%s" \
        % (acc0, min_acc)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_train_imagenet_network_flag_variants(tmp_path):
    """The --network dispatch covers the full symbols/ family: run one
    tiny epoch with resnext (grouped conv) and mobilenet (depthwise) on
    packed recordio data — the config-2 flow exercised for the round-3
    factories."""
    _example("image_classification", "train_imagenet.py")
    import train_imagenet
    from mxtpu.test_utils import make_rec

    rec = make_rec(str(tmp_path / "synth.rec"), 32, edge=40)
    for network in ("resnext", "resnet-v1"):
        speed = train_imagenet.main([
            "--data-train", rec, "--network", network, "--num-layers", "26"
            if network == "resnext" else "18",
            "--image-shape", "3,32,32", "--num-classes", "10",
            "--batch-size", "16", "--num-epochs", "1", "--kv-store",
            "local", "--speedometer-period", "1"])
        assert speed > 0, network


def test_model_parallel_lstm_gate():
    """group2ctx model parallelism end to end (parity:
    example/model-parallel-lstm/lstm.py): a 2-layer LSTM LM with layer
    groups placed on two devices trains and perplexity falls."""
    _example("rnn", "model_parallel_lstm.py")
    import model_parallel_lstm
    ppl = model_parallel_lstm.main(["--epochs", "3", "--n-tokens", "3000"])
    assert len(ppl) == 3
    assert ppl[-1] < ppl[0] * 0.97, "perplexity did not fall: %s" % (ppl,)


def test_sparse_linear_classification_gate():
    """Sparse pipeline end to end (parity: example/sparse/
    linear_classification.py): LibSVM csr batches + row_sparse weight via
    kvstore row_sparse_pull + server-side SGD; accuracy must climb well
    above chance."""
    _example("sparse", "linear_classification.py")
    import linear_classification
    accs = linear_classification.main(["--epochs", "5",
                                       "--num-examples", "512"])
    assert accs[-1] > 0.8, "sparse training reached only %s" % (accs,)


def test_adversary_fgsm_gate():
    """FGSM adversarial examples (parity: example/adversary): input-space
    gradients through the imperative tape — clean accuracy high, one
    signed-gradient step collapses it."""
    _example("adversary", "fgsm_mnist.py")
    import fgsm_mnist
    clean, adv = fgsm_mnist.main(["--epochs", "3", "--epsilon", "0.3",
                                  "--num-examples", "768"])
    assert clean > 0.95, clean
    assert adv < clean - 0.2, (clean, adv)


def test_text_cnn_gate():
    """Kim-CNN sentence classification (parity:
    example/cnn_text_classification): embedding + parallel conv widths +
    max-over-time through Module.fit; val accuracy > 0.9."""
    _example("cnn_text_classification", "text_cnn.py")
    import text_cnn
    acc = text_cnn.main(["--epochs", "4"])
    assert acc > 0.9, acc


def test_bi_lstm_sort_gate():
    """BidirectionalCell end to end (parity: example/bi-lstm-sort): a
    BiLSTM learns to emit the sorted input sequence — each position
    depends on the WHOLE sequence, so the backward direction must work;
    held-out token accuracy > 0.85."""
    _example("bi-lstm-sort", "sort_io.py")
    import sort_io
    acc = sort_io.main(["--epochs", "5", "--num-examples", "1536"])
    assert acc > 0.85, acc


def test_multitask_gate():
    """Two loss heads on one trunk via sym.Group (parity:
    example/multi-task): both tasks learn jointly."""
    _example("multi-task", "multitask_mnist.py")
    import multitask_mnist
    d, p = multitask_mnist.main(["--epochs", "4"])
    assert d > 0.95 and p > 0.95, (d, p)


def test_svm_output_gate():
    """SVMOutput hinge-loss head end to end (parity: example/svm_mnist):
    both the linear-hinge and squared-hinge variants train."""
    _example("svm_mnist", "svm_mnist.py")
    import svm_mnist
    assert svm_mnist.main(["--epochs", "4"]) > 0.95
    assert svm_mnist.main(["--epochs", "4", "--squared"]) > 0.95


def test_autoencoder_gate():
    """AE reconstruction through LinearRegressionOutput (parity:
    example/autoencoder): bottleneck reconstruction captures most of the
    low-rank data's power."""
    _example("autoencoder", "autoencoder.py")
    import autoencoder
    mse, var = autoencoder.main(["--epochs", "5"])
    assert mse < 0.35 * var, (mse, var)


def test_lstm_bucketing_fused_gate():
    """The fused variant (cudnn_lstm_bucketing.py parity: one multi-layer
    RNN op lowered to an XLA while loop) trains under BucketingModule."""
    _example("rnn", "lstm_bucketing.py")
    import mxtpu as mx
    import lstm_bucketing
    mx.random.seed(7)
    np.random.seed(7)  # NDArrayIter shuffle rides the global numpy RNG
    ppl = lstm_bucketing.main([
        "--fused", "--num-epochs", "8", "--num-hidden", "64",
        "--num-embed", "32"])
    assert min(ppl[2:]) < ppl[0] * 0.85, \
        "fused perplexity did not fall: %s" % (ppl,)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_nce_loss_gate():
    """NCE training (parity: example/nce-loss): binary noise-contrastive
    objective with unigram negatives; the NCE-trained embeddings beat the
    unigram baseline by a wide margin under FULL-softmax evaluation."""
    _example("nce-loss", "nce_lm.py")
    import nce_lm
    acc, base = nce_lm.main(["--epochs", "6", "--lr", "1.0"])
    assert acc > 3 * base, (acc, base)


def test_numpy_ops_custom_softmax_gate():
    """Custom-op softmax head (examples/numpy_ops/custom_softmax.py,
    parity example/numpy-ops/custom_softmax.py): the numpy CustomOp loss
    trains an MLP to >0.9 val accuracy through the host-callback path."""
    _example("numpy_ops", "custom_softmax.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import custom_softmax
    acc = custom_softmax.main(["--epochs", "6"])
    assert acc > 0.9, "custom-softmax MLP reached only %.3f" % acc


def test_recommenders_matrix_fact_gate():
    """Matrix factorization (examples/recommenders/matrix_fact.py, parity
    example/recommenders/matrix_fact.py): embeddings + inner product +
    LinearRegressionOutput recover low-rank ratings to RMSE < 0.35
    (ground-truth noise is 0.1; untrained is ~1.0)."""
    _example("recommenders", "matrix_fact.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import matrix_fact
    score = matrix_fact.main(["--epochs", "8"])
    assert score < 0.35, "MF val RMSE stuck at %.3f" % score


def test_gan_symbolic_gate():
    """Symbolic DCGAN (examples/gan/dcgan_sym.py, parity
    example/gan/dcgan.py): the Module-level GAN loop — inputs_need_grad,
    fake/real grad accumulation, G updated through D.get_input_grads() —
    must let the generator genuinely fool the discriminator at some
    point (min fake-detect accuracy < 0.9)."""
    _example("gan", "dcgan_sym.py")
    import mxtpu as mx
    import dcgan_sym
    mx.random.seed(7)
    np.random.seed(7)  # NDArrayIter shuffle draws from numpy's global RNG
    first_acc, min_acc = dcgan_sym.main(["--epochs", "3"])
    assert min_acc < 0.9, \
        "generator never fooled D: first=%s min=%s" % (first_acc, min_acc)


def test_fcn_xs_gate():
    """FCN segmentation (examples/fcn-xs/fcn_xs.py, parity
    example/fcn-xs/symbol_fcnxs.py): conv trunk + 1x1 score +
    Deconvolution upsample + Crop + multi_output SoftmaxOutput reaches
    >0.9 per-pixel accuracy on separable rectangles."""
    _example("fcn-xs", "fcn_xs.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import fcn_xs
    acc = fcn_xs.main(["--epochs", "12"])
    assert acc > 0.9, "fcn-xs pixel accuracy stuck at %.3f" % acc


def test_neural_style_gate():
    """Neural style (examples/neural-style/nstyle.py, parity
    example/neural-style/nstyle.py): input-space optimization against
    Gram/content targets — the weighted loss must fall by >60%."""
    _example("neural-style", "nstyle.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import nstyle
    first, last = nstyle.main(["--iters", "40"])
    assert last < first * 0.4, \
        "style loss barely moved: %.5f -> %.5f" % (first, last)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_dqn_gate():
    """DQN on the deterministic grid world (examples/reinforcement-learning/
    dqn.py, parity example/reinforcement-learning/dqn): replay + target net
    + TD regression must produce a greedy policy that reaches the goal —
    mean return over fixed starts > 0.5 (random policy is ~ -0.3)."""
    _example("reinforcement-learning", "dqn.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import dqn
    ret = dqn.main(["--updates", "400"])
    assert ret > 0.5, "greedy return stuck at %.3f" % ret


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_parallel_actor_critic_gate():
    """Parallel A2C on vectorized CartPole (examples/reinforcement-learning/
    parallel_actor_critic.py, parity example/reinforcement-learning/
    parallel_actor_critic): mean episode length over the last completed
    episodes must clear 50 (untrained policy balances ~10-25 steps)."""
    _example("reinforcement-learning", "parallel_actor_critic.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import parallel_actor_critic
    steps = parallel_actor_critic.main(["--iters", "250"])
    assert steps > 50, "episode length stuck at %.1f" % steps


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_stochastic_depth_gate():
    """Stochastic-depth residual net (examples/stochastic-depth/
    sd_cifar10.py, parity example/stochastic-depth): whole-branch Bernoulli
    gates via in-graph Dropout-on-ones train to >0.85 val accuracy, and the
    gates are identity at inference (deterministic eval)."""
    _example("stochastic-depth", "sd_cifar10.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import sd_cifar10
    acc = sd_cifar10.main(["--epochs", "8"])
    assert acc > 0.85, "stochastic-depth net reached only %.3f" % acc


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_dec_gate():
    """Deep Embedded Clustering (examples/dec/dec.py, parity
    example/dec/dec.py): AE pretrain + Student-t KL refinement with
    trainable centroids must reach >0.9 clustering accuracy on 4 blobs
    through a 2-D bottleneck."""
    _example("dec", "dec.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import dec
    acc = dec.main([])
    assert acc > 0.9, "DEC cluster accuracy stuck at %.3f" % acc


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_vae_gate():
    """Variational autoencoder (examples/vae/vae.py, parity example/vae):
    reparameterized ELBO training must cut the validation negative ELBO to
    under half its untrained value."""
    _example("vae", "vae.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import vae
    start, end = vae.main(["--epochs", "30"])
    assert end < 0.5 * start, "-ELBO %.2f -> %.2f (no real improvement)" \
        % (start, end)


def test_dsd_gate():
    """Dense-Sparse-Dense retraining (examples/dsd/dsd.py, parity
    example/dsd): magnitude pruning to 60% sparsity must actually zero the
    weights mid-phase, and the final re-densified model must hold the dense
    baseline's accuracy (within 2 points) or beat it."""
    _example("dsd", "dsd.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import dsd
    dense, sparse, final, frac_zero = dsd.main([])
    assert frac_zero > 0.55, "mask not applied: zero frac %.2f" % frac_zero
    assert final > 0.8, "DSD model never learned: final %.3f" % final
    assert final >= dense - 0.02, \
        "DSD lost accuracy: dense %.3f -> final %.3f" % (dense, final)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_speech_acoustic_gate():
    """Frame-level acoustic model (examples/speech-demo/speech_acoustic.py,
    parity example/speech-demo): BiLSTM over synthetic filterbank frames
    with per-frame cross-entropy must clear 0.9 frame accuracy (chance is
    ~0.17 over 6 phoneme classes)."""
    _example("speech-demo", "speech_acoustic.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import speech_acoustic
    acc = speech_acoustic.main(["--epochs", "8"])
    assert acc > 0.9, "frame accuracy stuck at %.3f" % acc


def test_sgld_bnn_gate():
    """SGLD Bayesian net (examples/bayesian-methods/sgld_bnn.py, parity
    example/bayesian-methods): posterior-ensemble prediction must classify
    two-moons >0.9 and be more uncertain off-distribution than on it."""
    _example("bayesian-methods", "sgld_bnn.py")
    import mxtpu as mx
    mx.random.seed(42)
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import sgld_bnn
    acc_single, acc_ens, h_mean, h_ens, spread = sgld_bnn.main(
        ["--epochs", "30", "--burn-in", "15", "--lr", "0.0003"])
    assert acc_ens > 0.9, "ensemble accuracy %.3f" % acc_ens
    assert spread > 1e-4, "posterior collapsed: weight spread %.5f" % spread
    # Jensen: mixture entropy dominates the mean per-sample entropy
    assert h_ens >= h_mean - 1e-6, \
        "mixture entropy %.3f below mean single %.3f" % (h_ens, h_mean)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_lstm_ocr_ctc_gate():
    """LSTM+CTC OCR (examples/ctc/lstm_ocr.py, parity example/ctc/
    lstm_ocr.py + example/captcha): an unrolled two-layer LSTM over image
    columns with the `_contrib_CTCLoss` head must read >0.8 of held-out
    variable-length digit strips exactly (greedy CTC decode)."""
    _example("ctc", "lstm_ocr.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import lstm_ocr
    acc = lstm_ocr.main(["--epochs", "25", "--lr", "0.01"])
    assert acc > 0.8, "OCR sequence accuracy stuck at %.3f" % acc


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_rcnn_gate():
    """Faster R-CNN (examples/rcnn/train_end2end.py, parity example/rcnn):
    RPN anchor losses + `_contrib_Proposal` + CustomOp proposal-target
    sampling + ROIPooling heads trained jointly must localize+classify
    >0.8 of synthetic single-object scenes (IoU>0.5, right class)."""
    _example("rcnn", "train_end2end.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import train_end2end
    acc = train_end2end.main(["--epochs", "6"])
    assert acc > 0.8, "rcnn detection accuracy stuck at %.3f" % acc


def test_python_loss_module_gate():
    """SequentialModule + PythonLossModule (examples/module/python_loss.py,
    parity example/module/python_loss.py): a numpy multiclass-hinge
    gradient injected behind a symbolic trunk trains to >0.9."""
    _example("module", "python_loss.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import python_loss
    acc = python_loss.main(["--epochs", "8"])
    assert acc > 0.9, "hinge-loss MLP stuck at %.3f" % acc


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_time_major_rnn_gate():
    """Time-major unroll (examples/rnn-time-major/rnn_cell_demo.py, parity
    example/rnn-time-major): LSTM LM over (T, N) batches converges toward
    the corpus noise floor."""
    _example("rnn-time-major", "rnn_cell_demo.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import rnn_cell_demo
    hist = rnn_cell_demo.main(["--epochs", "6"])
    assert hist[-1] < hist[0] * 0.6, "perplexity did not fall: %s" % hist
    assert hist[-1] < 2.2, "final perplexity %.2f above noise floor" % hist[-1]


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_profiler_matmul_example():
    """Profiler demo (examples/profiler/profiler_matmul.py, parity
    example/profiler): every dot in the chain gets a chrome-trace span."""
    import os
    import tempfile
    _example("profiler", "profiler_matmul.py")
    import profiler_matmul
    with tempfile.TemporaryDirectory() as d:
        spans, dots = profiler_matmul.main(
            ["--chain", "4", "--file", os.path.join(d, "t.json")])
    assert dots == 4, "expected 4 dot spans, saw %d (total %d)" % (dots, spans)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_memcost_example():
    """Residual-memory plans (examples/memcost/inception_memcost.py,
    parity example/memcost): block remat must cut the saved-activation
    bytes by >2x vs keep-all, and whole-forward mirror below block."""
    _example("memcost", "inception_memcost.py")
    import inception_memcost
    res = inception_memcost.main(["--batch-size", "4", "--image-size", "96"])
    keep = res["keep_all"]["act_mb"]
    block = res["block"]["act_mb"]
    mirror = res["mirror"]["act_mb"]
    assert block < keep / 2, "block remat saved nothing: %s" % (res,)
    assert mirror <= block, "mirror above block: %s" % (res,)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_torch_module_example_gate():
    """Torch-in-graph (examples/torch/torch_module.py, parity
    example/torch): a torch.nn block inside the Symbol trains to >0.9."""
    _example("torch", "torch_module.py")
    import mxtpu as mx
    mx.random.seed(42)  # deterministic init regardless of suite order
    np.random.seed(42)  # NDArrayIter shuffle draws from numpy's global RNG
    import torch_module
    acc = torch_module.main(["--epochs", "6"])
    assert acc > 0.9, "torch-in-graph accuracy stuck at %.3f" % acc


def test_python_howto_examples():
    """API how-tos (examples/python-howto/howtos.py, parity
    example/python-howto): monitor stats, multi-output Group, conv
    debugging, manual DataIter driving — all four mechanisms work."""
    _example("python-howto", "howtos.py")
    import howtos
    assert howtos.main() is True


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_adversarial_vae_gate():
    """VAE/GAN hybrid (examples/mxnet_adversarial_vae/vaegan.py, parity
    example/mxnet_adversarial_vae): three-way E/G/D training must drive
    reconstruction well below the data power while the discriminator
    falls from certainty toward equilibrium."""
    _example("mxnet_adversarial_vae", "vaegan.py")
    import vaegan
    # determinism comes from vaegan.main's own --seed (it reseeds both
    # RNGs first thing)
    d_accs, recs, mse, power = vaegan.main(["--epochs", "8"])
    assert mse < power / 4, "reconstruction never learned: %.3f vs %.3f" \
        % (mse, power)
    assert recs[-1] < recs[0] * 0.8, "recon loss did not fall: %s" % recs
    assert d_accs[-1] < 0.98, "D stayed certain: %s" % d_accs


@pytest.mark.parametrize("network,epochs,floor", [("mlp", 10, 0.9),
                                                  ("lenet", 8, 0.85)])
def test_caffe_net_gate(network, epochs, floor):
    """In-graph caffe layers (examples/caffe/caffe_net.py, parity
    example/caffe/caffe_net.py): MLP and LeNet composed from
    mx.sym.CaffeOp inline-prototxt layers must learn their synthetic
    tasks through Module.fit."""
    _example("caffe", "caffe_net.py")
    import caffe_net
    acc = caffe_net.main(["--network", network, "--epochs", str(epochs)])
    assert acc > floor, "caffe %s reached only %.3f" % (network, acc)


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note): the
# heaviest convergence gates run in the slow tier (-m slow) so the
# 870s window covers the whole suite instead of truncating mid-file
def test_kaggle_ndsb1_gate(tmp_path):
    """Full NDSB-1 recipe (examples/kaggle-ndsb1, parity
    example/kaggle-ndsb1): class-folder tree -> gen_img_list
    (stratified) -> im2rec pack -> ImageRecordIter train w/ checkpoint
    -> checkpoint predict -> kaggle submission csv."""
    import csv
    import subprocess

    import cv2

    _example("kaggle-ndsb1", "gen_img_list.py")
    import gen_img_list
    import predict_dsb
    import submission_dsb
    import train_dsb

    # synthetic "plankton": class = dominant color channel pattern
    rng = np.random.RandomState(5)
    classes = ["copepod", "diatom", "protist", "shrimp"]
    train_dir = tmp_path / "data" / "train"
    test_dir = tmp_path / "data" / "test"
    test_dir.mkdir(parents=True)
    for li, cls in enumerate(classes):
        sub = train_dir / cls
        sub.mkdir(parents=True)
        for i in range(24):
            img = (rng.rand(32, 32, 3) * 60).astype(int)
            img[..., li % 3] += 150
            if li == 3:  # 4th class: bright everywhere
                img += 120
            img = np.clip(img, 0, 255).astype("uint8")
            cv2.imwrite(str(sub / ("%s_%d.jpg" % (cls, i))), img)
    for i in range(12):
        li = i % 4
        img = (rng.rand(32, 32, 3) * 60).astype(int)
        img[..., li % 3] += 150
        if li == 3:
            img += 120
        img = np.clip(img, 0, 255).astype("uint8")
        cv2.imwrite(str(test_dir / ("t%03d.jpg" % i)), img)

    data = str(tmp_path / "data")
    gen_img_list.main(["--image-folder", str(train_dir),
                       "--out-folder", data, "--train", "--stratified"])
    gen_img_list.main(["--image-folder", str(test_dir),
                       "--out-folder", data, "--out-file", "test.lst"])
    # stratified split: every class in both lists
    for lst in ("tr.lst", "va.lst"):
        labels = {ln.split("\t")[1] for ln in open(os.path.join(data, lst))}
        assert len(labels) == 4, (lst, labels)

    im2rec = os.path.join(_ROOT, "tools", "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    for name, root in [("tr", str(train_dir)), ("va", str(train_dir)),
                       ("test", str(test_dir))]:
        r = subprocess.run(
            [sys.executable, im2rec, os.path.join(data, name), root,
             "--resize", "24"], capture_output=True, text=True, env=env)
        assert r.returncode == 0, r.stdout + r.stderr

    prefix = str(tmp_path / "models" / "dsb")
    os.makedirs(os.path.dirname(prefix))
    acc = train_dsb.main(["--data-dir", data, "--num-classes", "4",
                          "--edge", "24", "--batch-size", "24",
                          "--num-epochs", "25", "--width", "0.5",
                          "--optimizer", "adam", "--lr", "0.002",
                          "--model-prefix", prefix])
    assert acc > 0.8, "ndsb1 val accuracy only %.3f" % acc

    probs = predict_dsb.main(["--model-prefix", prefix, "--epoch", "25",
                              "--test-rec", os.path.join(data, "test.rec"),
                              "--num-classes", "4", "--edge", "24",
                              "--batch-size", "6",
                              "--out", str(tmp_path / "probs.npy")])
    assert probs.shape == (12, 4)

    out_csv = str(tmp_path / "submission.csv")
    submission_dsb.main(["--probs", str(tmp_path / "probs.npy"),
                         "--test-lst", os.path.join(data, "test.lst"),
                         "--classes", os.path.join(data, "classes.txt"),
                         "--out", out_csv])
    with open(out_csv) as f:
        rows = list(csv.reader(f))
    assert rows[0] == ["image"] + classes
    assert len(rows) == 13
    body = np.array([[float(x) for x in r[1:]] for r in rows[1:]])
    np.testing.assert_allclose(body.sum(axis=1), 1.0, atol=1e-4)


def test_kaggle_ndsb2_gate(tmp_path):
    """NDSB-2 recipe (examples/kaggle-ndsb2, parity
    example/kaggle-ndsb2): synthetic beating-heart studies ->
    Preprocessing (CSV tensors + CDF label encode) -> frame-diff LeNet
    through CSVIter/FeedForward/LogisticRegressionOutput with the CRPS
    metric; training must beat the predict-the-prior CRPS baseline."""
    import csv as _csv

    import cv2

    _example("kaggle-ndsb2", "Preprocessing.py")
    import Preprocessing
    import Train

    rng = np.random.RandomState(9)
    frames, edge, cdf = 8, 24, 40
    root = tmp_path / "train"
    root.mkdir()
    labels = []
    for s in range(24):
        sid = "s%03d" % s
        (root / sid).mkdir()
        base_r = rng.uniform(4, 9)       # diastole radius
        amp = rng.uniform(0.3, 0.6)      # contraction amount
        for t in range(frames):
            phase = np.cos(2 * np.pi * t / frames) * 0.5 + 0.5
            r = base_r * (1 - amp * phase)
            img = np.zeros((edge, edge), np.uint8)
            cv2.circle(img, (edge // 2, edge // 2), int(round(r)), 200,
                       -1)
            cv2.imwrite(str(root / sid / ("frame_%02d.png" % t)), img)
        area = np.pi * base_r ** 2
        labels.append((sid, area * (1 - amp) ** 2 / 20, area / 20))
    with open(root / "labels.csv", "w", newline="") as f:
        w = _csv.writer(f)
        w.writerow(["Id", "Systole", "Diastole"])
        for row in labels:
            w.writerow([row[0], "%.2f" % row[1], "%.2f" % row[2]])

    prefix = str(tmp_path / "train")
    cwd = os.getcwd()
    Preprocessing.main(["--root", str(root), "--out-prefix", prefix,
                        "--frames", str(frames), "--edge", str(edge),
                        "--cdf-dim", str(cdf)])
    assert os.path.exists("%s-%dx%d-data.csv" % (prefix, edge, edge))

    sys_score, dia_score = Train.main(
        ["--data-prefix", prefix, "--frames", str(frames),
         "--edge", str(edge), "--cdf-dim", str(cdf),
         "--num-filter", "12", "--batch-size", "12",
         "--num-epochs", "12", "--lr", "0.01"])

    # baseline: predicting the mean encoded target everywhere
    enc = np.loadtxt(prefix + "-systole.csv", delimiter=",")
    base = Train.CRPS(enc, np.tile(enc.mean(0), (enc.shape[0], 1)))
    assert sys_score < base * 0.6, (sys_score, base)
    assert dia_score < base * 0.8, (dia_score, base)
    assert os.getcwd() == cwd
