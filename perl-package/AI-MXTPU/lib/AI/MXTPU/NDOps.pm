# GENERATED by perl-package/gen_perl_ops.py — do not edit.
# One sub per registered op (307 ops). Regenerate after registry changes.
package AI::MXTPU::NDOps;
use strict;
use warnings;

sub Activation {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Activation', \@ins, %a);
}

sub BatchNorm {
    my @ins = splice(@_, 0, 5);
    my %a = @_;
    return AI::MXTPU::invoke('BatchNorm', \@ins, %a);
}

sub BatchNorm_v1 {
    my @ins = splice(@_, 0, 5);
    my %a = @_;
    return AI::MXTPU::invoke('BatchNorm_v1', \@ins, %a);
}

sub BilinearSampler {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('BilinearSampler', \@ins, %a);
}

sub BlockGrad {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('BlockGrad', \@ins, %a);
}

sub CTCLoss {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('CTCLoss', \@ins, %a);
}

sub Cast {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Cast', \@ins, %a);
}

sub Concat {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('Concat', $ins, %a);
}

sub Convolution {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('Convolution', \@ins, %a);
}

sub Convolution_v1 {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('Convolution_v1', \@ins, %a);
}

sub Correlation {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('Correlation', \@ins, %a);
}

sub Crop {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('Crop', $ins, %a);
}

sub Custom {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('Custom', $ins, %a);
}

sub Deconvolution {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('Deconvolution', \@ins, %a);
}

sub DeformableConvolution {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('DeformableConvolution', \@ins, %a);
}

sub DeformablePSROIPooling {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('DeformablePSROIPooling', \@ins, %a);
}

sub Dropout {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Dropout', \@ins, %a);
}

sub ElementWiseSum {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('ElementWiseSum', $ins, %a);
}

sub Embedding {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('Embedding', \@ins, %a);
}

sub Flatten {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Flatten', \@ins, %a);
}

sub FullyConnected {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('FullyConnected', \@ins, %a);
}

sub GridGenerator {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('GridGenerator', \@ins, %a);
}

sub IdentityAttachKLSparseReg {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('IdentityAttachKLSparseReg', \@ins, %a);
}

sub InstanceNorm {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('InstanceNorm', \@ins, %a);
}

sub L2Normalization {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('L2Normalization', \@ins, %a);
}

sub LRN {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('LRN', \@ins, %a);
}

sub LayerNorm {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('LayerNorm', \@ins, %a);
}

sub LeakyReLU {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('LeakyReLU', \@ins, %a);
}

sub LinearRegressionOutput {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('LinearRegressionOutput', \@ins, %a);
}

sub LogisticRegressionOutput {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('LogisticRegressionOutput', \@ins, %a);
}

sub MAERegressionOutput {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('MAERegressionOutput', \@ins, %a);
}

sub MakeLoss {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('MakeLoss', \@ins, %a);
}

sub MultiBoxDetection {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('MultiBoxDetection', \@ins, %a);
}

sub MultiBoxPrior {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('MultiBoxPrior', \@ins, %a);
}

sub MultiBoxTarget {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('MultiBoxTarget', \@ins, %a);
}

sub MultiProposal {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('MultiProposal', \@ins, %a);
}

sub PSROIPooling {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('PSROIPooling', \@ins, %a);
}

sub Pad {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Pad', \@ins, %a);
}

sub Pooling {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Pooling', \@ins, %a);
}

sub Pooling_v1 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Pooling_v1', \@ins, %a);
}

sub Proposal {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('Proposal', \@ins, %a);
}

sub RMSNorm {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('RMSNorm', \@ins, %a);
}

sub RNN {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('RNN', \@ins, %a);
}

sub ROIPooling {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('ROIPooling', \@ins, %a);
}

sub Reshape {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('Reshape', \@ins, %a);
}

sub SVMOutput {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('SVMOutput', \@ins, %a);
}

sub SequenceLast {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('SequenceLast', \@ins, %a);
}

sub SequenceMask {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('SequenceMask', \@ins, %a);
}

sub SequenceReverse {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('SequenceReverse', \@ins, %a);
}

sub SliceChannel {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('SliceChannel', \@ins, %a);
}

sub Softmax {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('Softmax', \@ins, %a);
}

sub SoftmaxActivation {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('SoftmaxActivation', \@ins, %a);
}

sub SoftmaxOutput {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('SoftmaxOutput', \@ins, %a);
}

sub SpatialTransformer {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('SpatialTransformer', \@ins, %a);
}

sub SwapAxis {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('SwapAxis', \@ins, %a);
}

sub UpSampling {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('UpSampling', $ins, %a);
}

sub _Custom {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('_Custom', $ins, %a);
}

sub _NoGradient {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_NoGradient', \@ins, %a);
}

sub _add {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_add', \@ins, %a);
}

sub _arange {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_arange', \@ins, %a);
}

sub _contrib_CTCLoss {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_CTCLoss', \@ins, %a);
}

sub _contrib_CausalConv1D {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_CausalConv1D', \@ins, %a);
}

sub _contrib_DeformableConvolution {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_DeformableConvolution', \@ins, %a);
}

sub _contrib_DeformablePSROIPooling {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_DeformablePSROIPooling', \@ins, %a);
}

sub _contrib_FlashAttention {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_FlashAttention', \@ins, %a);
}

sub _contrib_GatedDeltaRule {
    my @ins = splice(@_, 0, 5);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_GatedDeltaRule', \@ins, %a);
}

sub _contrib_HeadGate {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_HeadGate', \@ins, %a);
}

sub _contrib_HeadNormRotary {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_HeadNormRotary', \@ins, %a);
}

sub _contrib_MoEExperts {
    my @ins = splice(@_, 0, 6);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_MoEExperts', \@ins, %a);
}

sub _contrib_MoERouter {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_MoERouter', \@ins, %a);
}

sub _contrib_MultiBoxDetection {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_MultiBoxDetection', \@ins, %a);
}

sub _contrib_MultiBoxPrior {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_MultiBoxPrior', \@ins, %a);
}

sub _contrib_MultiBoxTarget {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_MultiBoxTarget', \@ins, %a);
}

sub _contrib_MultiProposal {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_MultiProposal', \@ins, %a);
}

sub _contrib_PSROIPooling {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_PSROIPooling', \@ins, %a);
}

sub _contrib_Proposal {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_Proposal', \@ins, %a);
}

sub _contrib_RotaryEmbedding {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_RotaryEmbedding', \@ins, %a);
}

sub _contrib_SSDScan {
    my @ins = splice(@_, 0, 6);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_SSDScan', \@ins, %a);
}

sub _contrib_count_sketch {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_count_sketch', \@ins, %a);
}

sub _contrib_ctc_loss {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_ctc_loss', \@ins, %a);
}

sub _contrib_dequantize {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_dequantize', \@ins, %a);
}

sub _contrib_fft {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_fft', \@ins, %a);
}

sub _contrib_ifft {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_ifft', \@ins, %a);
}

sub _contrib_krprod {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('_contrib_krprod', $ins, %a);
}

sub _contrib_quantize {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_contrib_quantize', \@ins, %a);
}

sub _copy {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_copy', \@ins, %a);
}

sub _crop_assign {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_crop_assign', \@ins, %a);
}

sub _crop_assign_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_crop_assign_scalar', \@ins, %a);
}

sub _div {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_div', \@ins, %a);
}

sub _div_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_div_scalar', \@ins, %a);
}

sub _equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_equal', \@ins, %a);
}

sub _equal_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_equal_scalar', \@ins, %a);
}

sub _full {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_full', \@ins, %a);
}

sub _grad_add {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_grad_add', \@ins, %a);
}

sub _greater {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_greater', \@ins, %a);
}

sub _greater_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_greater_equal', \@ins, %a);
}

sub _greater_equal_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_greater_equal_scalar', \@ins, %a);
}

sub _greater_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_greater_scalar', \@ins, %a);
}

sub _hypot {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_hypot', \@ins, %a);
}

sub _hypot_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_hypot_scalar', \@ins, %a);
}

sub _identity_with_attr_like_rhs {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_identity_with_attr_like_rhs', \@ins, %a);
}

sub _khatri_rao {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('_khatri_rao', $ins, %a);
}

sub _lesser {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_lesser', \@ins, %a);
}

sub _lesser_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_lesser_equal', \@ins, %a);
}

sub _lesser_equal_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_lesser_equal_scalar', \@ins, %a);
}

sub _lesser_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_lesser_scalar', \@ins, %a);
}

sub _linalg_gelqf {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_gelqf', \@ins, %a);
}

sub _linalg_gemm {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_gemm', \@ins, %a);
}

sub _linalg_gemm2 {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_gemm2', \@ins, %a);
}

sub _linalg_potrf {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_potrf', \@ins, %a);
}

sub _linalg_potri {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_potri', \@ins, %a);
}

sub _linalg_sumlogdiag {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_sumlogdiag', \@ins, %a);
}

sub _linalg_syrk {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_syrk', \@ins, %a);
}

sub _linalg_trmm {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_trmm', \@ins, %a);
}

sub _linalg_trsm {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_linalg_trsm', \@ins, %a);
}

sub _maximum {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_maximum', \@ins, %a);
}

sub _maximum_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_maximum_scalar', \@ins, %a);
}

sub _minimum {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_minimum', \@ins, %a);
}

sub _minimum_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_minimum_scalar', \@ins, %a);
}

sub _minus {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_minus', \@ins, %a);
}

sub _minus_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_minus_scalar', \@ins, %a);
}

sub _mod {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_mod', \@ins, %a);
}

sub _mod_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_mod_scalar', \@ins, %a);
}

sub _mul {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_mul', \@ins, %a);
}

sub _mul_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_mul_scalar', \@ins, %a);
}

sub _not_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_not_equal', \@ins, %a);
}

sub _not_equal_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_not_equal_scalar', \@ins, %a);
}

sub _ones {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_ones', \@ins, %a);
}

sub _plus {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_plus', \@ins, %a);
}

sub _plus_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_plus_scalar', \@ins, %a);
}

sub _power {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_power', \@ins, %a);
}

sub _power_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_power_scalar', \@ins, %a);
}

sub _random_exponential {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_exponential', \@ins, %a);
}

sub _random_gamma {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_gamma', \@ins, %a);
}

sub _random_generalized_negative_binomial {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_generalized_negative_binomial', \@ins, %a);
}

sub _random_negative_binomial {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_negative_binomial', \@ins, %a);
}

sub _random_normal {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_normal', \@ins, %a);
}

sub _random_poisson {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_poisson', \@ins, %a);
}

sub _random_uniform {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_random_uniform', \@ins, %a);
}

sub _rdiv_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_rdiv_scalar', \@ins, %a);
}

sub _rminus_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_rminus_scalar', \@ins, %a);
}

sub _rmod_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_rmod_scalar', \@ins, %a);
}

sub _rpower_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_rpower_scalar', \@ins, %a);
}

sub _slice_assign {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_slice_assign', \@ins, %a);
}

sub _slice_assign_scalar {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_slice_assign_scalar', \@ins, %a);
}

sub _square_sum {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('_square_sum', \@ins, %a);
}

sub _sub {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('_sub', \@ins, %a);
}

sub _sum {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('_sum', $ins, %a);
}

sub _zeros {
    my @ins = splice(@_, 0, 0);
    my %a = @_;
    return AI::MXTPU::invoke('_zeros', \@ins, %a);
}

sub abs {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('abs', \@ins, %a);
}

sub adam_update {
    my @ins = splice(@_, 0, 4);
    my %a = @_;
    return AI::MXTPU::invoke('adam_update', \@ins, %a);
}

sub add_n {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('add_n', $ins, %a);
}

sub arccos {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('arccos', \@ins, %a);
}

sub arccosh {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('arccosh', \@ins, %a);
}

sub arcsin {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('arcsin', \@ins, %a);
}

sub arcsinh {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('arcsinh', \@ins, %a);
}

sub arctan {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('arctan', \@ins, %a);
}

sub arctanh {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('arctanh', \@ins, %a);
}

sub argmax {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('argmax', \@ins, %a);
}

sub argmax_channel {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('argmax_channel', \@ins, %a);
}

sub argmin {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('argmin', \@ins, %a);
}

sub argsort {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('argsort', \@ins, %a);
}

sub batch_dot {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('batch_dot', \@ins, %a);
}

sub batch_take {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('batch_take', \@ins, %a);
}

sub broadcast_add {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_add', \@ins, %a);
}

sub broadcast_axes {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_axes', \@ins, %a);
}

sub broadcast_axis {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_axis', \@ins, %a);
}

sub broadcast_div {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_div', \@ins, %a);
}

sub broadcast_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_equal', \@ins, %a);
}

sub broadcast_greater {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_greater', \@ins, %a);
}

sub broadcast_greater_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_greater_equal', \@ins, %a);
}

sub broadcast_hypot {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_hypot', \@ins, %a);
}

sub broadcast_lesser {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_lesser', \@ins, %a);
}

sub broadcast_lesser_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_lesser_equal', \@ins, %a);
}

sub broadcast_maximum {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_maximum', \@ins, %a);
}

sub broadcast_minimum {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_minimum', \@ins, %a);
}

sub broadcast_minus {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_minus', \@ins, %a);
}

sub broadcast_mod {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_mod', \@ins, %a);
}

sub broadcast_mul {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_mul', \@ins, %a);
}

sub broadcast_not_equal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_not_equal', \@ins, %a);
}

sub broadcast_plus {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_plus', \@ins, %a);
}

sub broadcast_power {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_power', \@ins, %a);
}

sub broadcast_sub {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_sub', \@ins, %a);
}

sub broadcast_to {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('broadcast_to', \@ins, %a);
}

sub cast {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('cast', \@ins, %a);
}

sub cast_storage {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('cast_storage', \@ins, %a);
}

sub causal_conv1d {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('causal_conv1d', \@ins, %a);
}

sub cbrt {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('cbrt', \@ins, %a);
}

sub ceil {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('ceil', \@ins, %a);
}

sub clip {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('clip', \@ins, %a);
}

sub concat {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('concat', $ins, %a);
}

sub cos {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('cos', \@ins, %a);
}

sub cosh {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('cosh', \@ins, %a);
}

sub crop {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('crop', \@ins, %a);
}

sub ctc_loss {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('ctc_loss', \@ins, %a);
}

sub degrees {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('degrees', \@ins, %a);
}

sub dequantize_int8 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('dequantize_int8', \@ins, %a);
}

sub dot {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('dot', \@ins, %a);
}

sub elemwise_add {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('elemwise_add', \@ins, %a);
}

sub elemwise_div {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('elemwise_div', \@ins, %a);
}

sub elemwise_mul {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('elemwise_mul', \@ins, %a);
}

sub elemwise_sub {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('elemwise_sub', \@ins, %a);
}

sub erf {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('erf', \@ins, %a);
}

sub exp {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('exp', \@ins, %a);
}

sub expand_dims {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('expand_dims', \@ins, %a);
}

sub expm1 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('expm1', \@ins, %a);
}

sub fix {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('fix', \@ins, %a);
}

sub flash_attention {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('flash_attention', \@ins, %a);
}

sub flatten {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('flatten', \@ins, %a);
}

sub flip {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('flip', \@ins, %a);
}

sub floor {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('floor', \@ins, %a);
}

sub ftrl_update {
    my @ins = splice(@_, 0, 4);
    my %a = @_;
    return AI::MXTPU::invoke('ftrl_update', \@ins, %a);
}

sub gamma {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('gamma', \@ins, %a);
}

sub gammaln {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('gammaln', \@ins, %a);
}

sub gated_delta_rule {
    my @ins = splice(@_, 0, 5);
    my %a = @_;
    return AI::MXTPU::invoke('gated_delta_rule', \@ins, %a);
}

sub gather_nd {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('gather_nd', \@ins, %a);
}

sub head_gate {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('head_gate', \@ins, %a);
}

sub head_norm_rotary {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('head_norm_rotary', \@ins, %a);
}

sub identity {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('identity', \@ins, %a);
}

sub khatri_rao {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('khatri_rao', $ins, %a);
}

sub linalg_gelqf {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_gelqf', \@ins, %a);
}

sub linalg_gemm {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_gemm', \@ins, %a);
}

sub linalg_gemm2 {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_gemm2', \@ins, %a);
}

sub linalg_potrf {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_potrf', \@ins, %a);
}

sub linalg_potri {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_potri', \@ins, %a);
}

sub linalg_sumlogdiag {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_sumlogdiag', \@ins, %a);
}

sub linalg_syrk {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_syrk', \@ins, %a);
}

sub linalg_trmm {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_trmm', \@ins, %a);
}

sub linalg_trsm {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('linalg_trsm', \@ins, %a);
}

sub log {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('log', \@ins, %a);
}

sub log10 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('log10', \@ins, %a);
}

sub log1p {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('log1p', \@ins, %a);
}

sub log2 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('log2', \@ins, %a);
}

sub log_softmax {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('log_softmax', \@ins, %a);
}

sub make_loss {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('make_loss', \@ins, %a);
}

sub max {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('max', \@ins, %a);
}

sub mean {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('mean', \@ins, %a);
}

sub min {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('min', \@ins, %a);
}

sub moe_experts {
    my @ins = splice(@_, 0, 6);
    my %a = @_;
    return AI::MXTPU::invoke('moe_experts', \@ins, %a);
}

sub moe_router {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('moe_router', \@ins, %a);
}

sub mp_sgd_mom_update {
    my @ins = splice(@_, 0, 4);
    my %a = @_;
    return AI::MXTPU::invoke('mp_sgd_mom_update', \@ins, %a);
}

sub mp_sgd_update {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('mp_sgd_update', \@ins, %a);
}

sub nanprod {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('nanprod', \@ins, %a);
}

sub nansum {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('nansum', \@ins, %a);
}

sub negative {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('negative', \@ins, %a);
}

sub norm {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('norm', \@ins, %a);
}

sub one_hot {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('one_hot', \@ins, %a);
}

sub ones_like {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('ones_like', \@ins, %a);
}

sub pad {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('pad', \@ins, %a);
}

sub pick {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('pick', \@ins, %a);
}

sub prod {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('prod', \@ins, %a);
}

sub quantize_int8 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('quantize_int8', \@ins, %a);
}

sub radians {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('radians', \@ins, %a);
}

sub rcbrt {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('rcbrt', \@ins, %a);
}

sub reciprocal {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('reciprocal', \@ins, %a);
}

sub relu {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('relu', \@ins, %a);
}

sub repeat {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('repeat', \@ins, %a);
}

sub reshape {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('reshape', \@ins, %a);
}

sub reshape_like {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('reshape_like', \@ins, %a);
}

sub reverse {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('reverse', \@ins, %a);
}

sub rint {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('rint', \@ins, %a);
}

sub rmsprop_update {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('rmsprop_update', \@ins, %a);
}

sub rmspropalex_update {
    my @ins = splice(@_, 0, 5);
    my %a = @_;
    return AI::MXTPU::invoke('rmspropalex_update', \@ins, %a);
}

sub rotary_embedding {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('rotary_embedding', \@ins, %a);
}

sub round {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('round', \@ins, %a);
}

sub rsqrt {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('rsqrt', \@ins, %a);
}

sub sample_exponential {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sample_exponential', \@ins, %a);
}

sub sample_gamma {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('sample_gamma', \@ins, %a);
}

sub sample_generalized_negative_binomial {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('sample_generalized_negative_binomial', \@ins, %a);
}

sub sample_multinomial {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sample_multinomial', \@ins, %a);
}

sub sample_negative_binomial {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('sample_negative_binomial', \@ins, %a);
}

sub sample_normal {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('sample_normal', \@ins, %a);
}

sub sample_poisson {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sample_poisson', \@ins, %a);
}

sub sample_uniform {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('sample_uniform', \@ins, %a);
}

sub scatter_nd {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('scatter_nd', \@ins, %a);
}

sub sgd_mom_update {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('sgd_mom_update', \@ins, %a);
}

sub sgd_update {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('sgd_update', \@ins, %a);
}

sub sigmoid {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sigmoid', \@ins, %a);
}

sub sign {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sign', \@ins, %a);
}

sub sin {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sin', \@ins, %a);
}

sub sinh {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sinh', \@ins, %a);
}

sub slice {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('slice', \@ins, %a);
}

sub slice_axis {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('slice_axis', \@ins, %a);
}

sub smooth_l1 {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('smooth_l1', \@ins, %a);
}

sub softmax {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('softmax', \@ins, %a);
}

sub softmax_cross_entropy {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('softmax_cross_entropy', \@ins, %a);
}

sub softsign {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('softsign', \@ins, %a);
}

sub sort {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sort', \@ins, %a);
}

sub space_to_depth {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('space_to_depth', \@ins, %a);
}

sub split {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('split', \@ins, %a);
}

sub sqrt {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sqrt', \@ins, %a);
}

sub square {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('square', \@ins, %a);
}

sub ssd_scan {
    my @ins = splice(@_, 0, 6);
    my %a = @_;
    return AI::MXTPU::invoke('ssd_scan', \@ins, %a);
}

sub stack {
    my ($ins, %a) = @_;
    return AI::MXTPU::invoke('stack', $ins, %a);
}

sub stop_gradient {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('stop_gradient', \@ins, %a);
}

sub sum {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sum', \@ins, %a);
}

sub sum_axis {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('sum_axis', \@ins, %a);
}

sub swapaxes {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('swapaxes', \@ins, %a);
}

sub take {
    my @ins = splice(@_, 0, 2);
    my %a = @_;
    return AI::MXTPU::invoke('take', \@ins, %a);
}

sub tan {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('tan', \@ins, %a);
}

sub tanh {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('tanh', \@ins, %a);
}

sub tile {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('tile', \@ins, %a);
}

sub topk {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('topk', \@ins, %a);
}

sub transpose {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('transpose', \@ins, %a);
}

sub trunc {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('trunc', \@ins, %a);
}

sub where {
    my @ins = splice(@_, 0, 3);
    my %a = @_;
    return AI::MXTPU::invoke('where', \@ins, %a);
}

sub zeros_like {
    my @ins = splice(@_, 0, 1);
    my %a = @_;
    return AI::MXTPU::invoke('zeros_like', \@ins, %a);
}

1;
