# GENERATED by perl-package/gen_perl_ops.py — do not edit.
# One sub per registered op (307 ops). Regenerate after registry changes.
package AI::MXTPU::Ops;
use strict;
use warnings;

sub Activation {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Activation', \%in, %a);
}

sub BatchNorm {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    $in{'beta'} = delete $a{'beta'} if exists $a{'beta'};
    $in{'moving_mean'} = delete $a{'moving_mean'} if exists $a{'moving_mean'};
    $in{'moving_var'} = delete $a{'moving_var'} if exists $a{'moving_var'};
    return AI::MXTPU::Symbol->create('BatchNorm', \%in, %a);
}

sub BatchNorm_v1 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    $in{'beta'} = delete $a{'beta'} if exists $a{'beta'};
    $in{'moving_mean'} = delete $a{'moving_mean'} if exists $a{'moving_mean'};
    $in{'moving_var'} = delete $a{'moving_var'} if exists $a{'moving_var'};
    return AI::MXTPU::Symbol->create('BatchNorm_v1', \%in, %a);
}

sub BilinearSampler {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'grid'} = delete $a{'grid'} if exists $a{'grid'};
    return AI::MXTPU::Symbol->create('BilinearSampler', \%in, %a);
}

sub BlockGrad {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('BlockGrad', \%in, %a);
}

sub CTCLoss {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('CTCLoss', \%in, %a);
}

sub Cast {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Cast', \%in, %a);
}

sub Concat {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('Concat', $ins, %a);
}

sub Convolution {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'bias'} = delete $a{'bias'} if exists $a{'bias'};
    return AI::MXTPU::Symbol->create('Convolution', \%in, %a);
}

sub Convolution_v1 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'bias'} = delete $a{'bias'} if exists $a{'bias'};
    return AI::MXTPU::Symbol->create('Convolution_v1', \%in, %a);
}

sub Correlation {
    my %a = @_;
    my %in;
    $in{'data1'} = delete $a{'data1'} if exists $a{'data1'};
    $in{'data2'} = delete $a{'data2'} if exists $a{'data2'};
    return AI::MXTPU::Symbol->create('Correlation', \%in, %a);
}

sub Crop {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('Crop', $ins, %a);
}

sub Custom {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('Custom', $ins, %a);
}

sub Deconvolution {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('Deconvolution', \%in, %a);
}

sub DeformableConvolution {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'offset'} = delete $a{'offset'} if exists $a{'offset'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('DeformableConvolution', \%in, %a);
}

sub DeformablePSROIPooling {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'rois'} = delete $a{'rois'} if exists $a{'rois'};
    $in{'trans'} = delete $a{'trans'} if exists $a{'trans'};
    return AI::MXTPU::Symbol->create('DeformablePSROIPooling', \%in, %a);
}

sub Dropout {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Dropout', \%in, %a);
}

sub ElementWiseSum {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('ElementWiseSum', $ins, %a);
}

sub Embedding {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('Embedding', \%in, %a);
}

sub Flatten {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Flatten', \%in, %a);
}

sub FullyConnected {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'bias'} = delete $a{'bias'} if exists $a{'bias'};
    return AI::MXTPU::Symbol->create('FullyConnected', \%in, %a);
}

sub GridGenerator {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('GridGenerator', \%in, %a);
}

sub IdentityAttachKLSparseReg {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('IdentityAttachKLSparseReg', \%in, %a);
}

sub InstanceNorm {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    $in{'beta'} = delete $a{'beta'} if exists $a{'beta'};
    return AI::MXTPU::Symbol->create('InstanceNorm', \%in, %a);
}

sub L2Normalization {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('L2Normalization', \%in, %a);
}

sub LRN {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('LRN', \%in, %a);
}

sub LayerNorm {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    $in{'beta'} = delete $a{'beta'} if exists $a{'beta'};
    return AI::MXTPU::Symbol->create('LayerNorm', \%in, %a);
}

sub LeakyReLU {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('LeakyReLU', \%in, %a);
}

sub LinearRegressionOutput {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('LinearRegressionOutput', \%in, %a);
}

sub LogisticRegressionOutput {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('LogisticRegressionOutput', \%in, %a);
}

sub MAERegressionOutput {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('MAERegressionOutput', \%in, %a);
}

sub MakeLoss {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('MakeLoss', \%in, %a);
}

sub MultiBoxDetection {
    my %a = @_;
    my %in;
    $in{'cls_prob'} = delete $a{'cls_prob'} if exists $a{'cls_prob'};
    $in{'loc_pred'} = delete $a{'loc_pred'} if exists $a{'loc_pred'};
    $in{'anchor'} = delete $a{'anchor'} if exists $a{'anchor'};
    return AI::MXTPU::Symbol->create('MultiBoxDetection', \%in, %a);
}

sub MultiBoxPrior {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('MultiBoxPrior', \%in, %a);
}

sub MultiBoxTarget {
    my %a = @_;
    my %in;
    $in{'anchor'} = delete $a{'anchor'} if exists $a{'anchor'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    $in{'cls_pred'} = delete $a{'cls_pred'} if exists $a{'cls_pred'};
    return AI::MXTPU::Symbol->create('MultiBoxTarget', \%in, %a);
}

sub MultiProposal {
    my %a = @_;
    my %in;
    $in{'cls_prob'} = delete $a{'cls_prob'} if exists $a{'cls_prob'};
    $in{'bbox_pred'} = delete $a{'bbox_pred'} if exists $a{'bbox_pred'};
    $in{'im_info'} = delete $a{'im_info'} if exists $a{'im_info'};
    return AI::MXTPU::Symbol->create('MultiProposal', \%in, %a);
}

sub PSROIPooling {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'rois'} = delete $a{'rois'} if exists $a{'rois'};
    return AI::MXTPU::Symbol->create('PSROIPooling', \%in, %a);
}

sub Pad {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Pad', \%in, %a);
}

sub Pooling {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Pooling', \%in, %a);
}

sub Pooling_v1 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Pooling_v1', \%in, %a);
}

sub Proposal {
    my %a = @_;
    my %in;
    $in{'cls_prob'} = delete $a{'cls_prob'} if exists $a{'cls_prob'};
    $in{'bbox_pred'} = delete $a{'bbox_pred'} if exists $a{'bbox_pred'};
    $in{'im_info'} = delete $a{'im_info'} if exists $a{'im_info'};
    return AI::MXTPU::Symbol->create('Proposal', \%in, %a);
}

sub RMSNorm {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    return AI::MXTPU::Symbol->create('RMSNorm', \%in, %a);
}

sub RNN {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'parameters'} = delete $a{'parameters'} if exists $a{'parameters'};
    $in{'state'} = delete $a{'state'} if exists $a{'state'};
    return AI::MXTPU::Symbol->create('RNN', \%in, %a);
}

sub ROIPooling {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'rois'} = delete $a{'rois'} if exists $a{'rois'};
    return AI::MXTPU::Symbol->create('ROIPooling', \%in, %a);
}

sub Reshape {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('Reshape', \%in, %a);
}

sub SVMOutput {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('SVMOutput', \%in, %a);
}

sub SequenceLast {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('SequenceLast', \%in, %a);
}

sub SequenceMask {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('SequenceMask', \%in, %a);
}

sub SequenceReverse {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('SequenceReverse', \%in, %a);
}

sub SliceChannel {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('SliceChannel', \%in, %a);
}

sub Softmax {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('Softmax', \%in, %a);
}

sub SoftmaxActivation {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('SoftmaxActivation', \%in, %a);
}

sub SoftmaxOutput {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('SoftmaxOutput', \%in, %a);
}

sub SpatialTransformer {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'loc'} = delete $a{'loc'} if exists $a{'loc'};
    return AI::MXTPU::Symbol->create('SpatialTransformer', \%in, %a);
}

sub SwapAxis {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('SwapAxis', \%in, %a);
}

sub UpSampling {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('UpSampling', $ins, %a);
}

sub _Custom {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('_Custom', $ins, %a);
}

sub _NoGradient {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_NoGradient', \%in, %a);
}

sub _add {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_add', \%in, %a);
}

sub _arange {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_arange', \%in, %a);
}

sub _contrib_CTCLoss {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('_contrib_CTCLoss', \%in, %a);
}

sub _contrib_CausalConv1D {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('_contrib_CausalConv1D', \%in, %a);
}

sub _contrib_DeformableConvolution {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'offset'} = delete $a{'offset'} if exists $a{'offset'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('_contrib_DeformableConvolution', \%in, %a);
}

sub _contrib_DeformablePSROIPooling {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'rois'} = delete $a{'rois'} if exists $a{'rois'};
    $in{'trans'} = delete $a{'trans'} if exists $a{'trans'};
    return AI::MXTPU::Symbol->create('_contrib_DeformablePSROIPooling', \%in, %a);
}

sub _contrib_FlashAttention {
    my %a = @_;
    my %in;
    $in{'query'} = delete $a{'query'} if exists $a{'query'};
    $in{'key'} = delete $a{'key'} if exists $a{'key'};
    $in{'value'} = delete $a{'value'} if exists $a{'value'};
    return AI::MXTPU::Symbol->create('_contrib_FlashAttention', \%in, %a);
}

sub _contrib_GatedDeltaRule {
    my %a = @_;
    my %in;
    $in{'query'} = delete $a{'query'} if exists $a{'query'};
    $in{'key'} = delete $a{'key'} if exists $a{'key'};
    $in{'value'} = delete $a{'value'} if exists $a{'value'};
    $in{'g'} = delete $a{'g'} if exists $a{'g'};
    $in{'beta'} = delete $a{'beta'} if exists $a{'beta'};
    return AI::MXTPU::Symbol->create('_contrib_GatedDeltaRule', \%in, %a);
}

sub _contrib_HeadGate {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gate'} = delete $a{'gate'} if exists $a{'gate'};
    return AI::MXTPU::Symbol->create('_contrib_HeadGate', \%in, %a);
}

sub _contrib_HeadNormRotary {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    return AI::MXTPU::Symbol->create('_contrib_HeadNormRotary', \%in, %a);
}

sub _contrib_MoEExperts {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'topk_weight'} = delete $a{'topk_weight'} if exists $a{'topk_weight'};
    $in{'topk_index'} = delete $a{'topk_index'} if exists $a{'topk_index'};
    $in{'gate_weight'} = delete $a{'gate_weight'} if exists $a{'gate_weight'};
    $in{'up_weight'} = delete $a{'up_weight'} if exists $a{'up_weight'};
    $in{'down_weight'} = delete $a{'down_weight'} if exists $a{'down_weight'};
    return AI::MXTPU::Symbol->create('_contrib_MoEExperts', \%in, %a);
}

sub _contrib_MoERouter {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('_contrib_MoERouter', \%in, %a);
}

sub _contrib_MultiBoxDetection {
    my %a = @_;
    my %in;
    $in{'cls_prob'} = delete $a{'cls_prob'} if exists $a{'cls_prob'};
    $in{'loc_pred'} = delete $a{'loc_pred'} if exists $a{'loc_pred'};
    $in{'anchor'} = delete $a{'anchor'} if exists $a{'anchor'};
    return AI::MXTPU::Symbol->create('_contrib_MultiBoxDetection', \%in, %a);
}

sub _contrib_MultiBoxPrior {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_contrib_MultiBoxPrior', \%in, %a);
}

sub _contrib_MultiBoxTarget {
    my %a = @_;
    my %in;
    $in{'anchor'} = delete $a{'anchor'} if exists $a{'anchor'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    $in{'cls_pred'} = delete $a{'cls_pred'} if exists $a{'cls_pred'};
    return AI::MXTPU::Symbol->create('_contrib_MultiBoxTarget', \%in, %a);
}

sub _contrib_MultiProposal {
    my %a = @_;
    my %in;
    $in{'cls_prob'} = delete $a{'cls_prob'} if exists $a{'cls_prob'};
    $in{'bbox_pred'} = delete $a{'bbox_pred'} if exists $a{'bbox_pred'};
    $in{'im_info'} = delete $a{'im_info'} if exists $a{'im_info'};
    return AI::MXTPU::Symbol->create('_contrib_MultiProposal', \%in, %a);
}

sub _contrib_PSROIPooling {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'rois'} = delete $a{'rois'} if exists $a{'rois'};
    return AI::MXTPU::Symbol->create('_contrib_PSROIPooling', \%in, %a);
}

sub _contrib_Proposal {
    my %a = @_;
    my %in;
    $in{'cls_prob'} = delete $a{'cls_prob'} if exists $a{'cls_prob'};
    $in{'bbox_pred'} = delete $a{'bbox_pred'} if exists $a{'bbox_pred'};
    $in{'im_info'} = delete $a{'im_info'} if exists $a{'im_info'};
    return AI::MXTPU::Symbol->create('_contrib_Proposal', \%in, %a);
}

sub _contrib_RotaryEmbedding {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_contrib_RotaryEmbedding', \%in, %a);
}

sub _contrib_SSDScan {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'dt'} = delete $a{'dt'} if exists $a{'dt'};
    $in{'A_log'} = delete $a{'A_log'} if exists $a{'A_log'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    $in{'C'} = delete $a{'C'} if exists $a{'C'};
    $in{'D'} = delete $a{'D'} if exists $a{'D'};
    return AI::MXTPU::Symbol->create('_contrib_SSDScan', \%in, %a);
}

sub _contrib_count_sketch {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'h'} = delete $a{'h'} if exists $a{'h'};
    $in{'s'} = delete $a{'s'} if exists $a{'s'};
    return AI::MXTPU::Symbol->create('_contrib_count_sketch', \%in, %a);
}

sub _contrib_ctc_loss {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('_contrib_ctc_loss', \%in, %a);
}

sub _contrib_dequantize {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'min_range'} = delete $a{'min_range'} if exists $a{'min_range'};
    $in{'max_range'} = delete $a{'max_range'} if exists $a{'max_range'};
    return AI::MXTPU::Symbol->create('_contrib_dequantize', \%in, %a);
}

sub _contrib_fft {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_contrib_fft', \%in, %a);
}

sub _contrib_ifft {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_contrib_ifft', \%in, %a);
}

sub _contrib_krprod {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('_contrib_krprod', $ins, %a);
}

sub _contrib_quantize {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'min_range'} = delete $a{'min_range'} if exists $a{'min_range'};
    $in{'max_range'} = delete $a{'max_range'} if exists $a{'max_range'};
    return AI::MXTPU::Symbol->create('_contrib_quantize', \%in, %a);
}

sub _copy {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_copy', \%in, %a);
}

sub _crop_assign {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_crop_assign', \%in, %a);
}

sub _crop_assign_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_crop_assign_scalar', \%in, %a);
}

sub _div {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_div', \%in, %a);
}

sub _div_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_div_scalar', \%in, %a);
}

sub _equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_equal', \%in, %a);
}

sub _equal_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_equal_scalar', \%in, %a);
}

sub _full {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_full', \%in, %a);
}

sub _grad_add {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_grad_add', \%in, %a);
}

sub _greater {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_greater', \%in, %a);
}

sub _greater_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_greater_equal', \%in, %a);
}

sub _greater_equal_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_greater_equal_scalar', \%in, %a);
}

sub _greater_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_greater_scalar', \%in, %a);
}

sub _hypot {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_hypot', \%in, %a);
}

sub _hypot_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_hypot_scalar', \%in, %a);
}

sub _identity_with_attr_like_rhs {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_identity_with_attr_like_rhs', \%in, %a);
}

sub _khatri_rao {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('_khatri_rao', $ins, %a);
}

sub _lesser {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_lesser', \%in, %a);
}

sub _lesser_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_lesser_equal', \%in, %a);
}

sub _lesser_equal_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_lesser_equal_scalar', \%in, %a);
}

sub _lesser_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_lesser_scalar', \%in, %a);
}

sub _linalg_gelqf {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('_linalg_gelqf', \%in, %a);
}

sub _linalg_gemm {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    $in{'C'} = delete $a{'C'} if exists $a{'C'};
    return AI::MXTPU::Symbol->create('_linalg_gemm', \%in, %a);
}

sub _linalg_gemm2 {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    return AI::MXTPU::Symbol->create('_linalg_gemm2', \%in, %a);
}

sub _linalg_potrf {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('_linalg_potrf', \%in, %a);
}

sub _linalg_potri {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('_linalg_potri', \%in, %a);
}

sub _linalg_sumlogdiag {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('_linalg_sumlogdiag', \%in, %a);
}

sub _linalg_syrk {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('_linalg_syrk', \%in, %a);
}

sub _linalg_trmm {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    return AI::MXTPU::Symbol->create('_linalg_trmm', \%in, %a);
}

sub _linalg_trsm {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    return AI::MXTPU::Symbol->create('_linalg_trsm', \%in, %a);
}

sub _maximum {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_maximum', \%in, %a);
}

sub _maximum_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_maximum_scalar', \%in, %a);
}

sub _minimum {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_minimum', \%in, %a);
}

sub _minimum_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_minimum_scalar', \%in, %a);
}

sub _minus {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_minus', \%in, %a);
}

sub _minus_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_minus_scalar', \%in, %a);
}

sub _mod {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_mod', \%in, %a);
}

sub _mod_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_mod_scalar', \%in, %a);
}

sub _mul {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_mul', \%in, %a);
}

sub _mul_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_mul_scalar', \%in, %a);
}

sub _not_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_not_equal', \%in, %a);
}

sub _not_equal_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_not_equal_scalar', \%in, %a);
}

sub _ones {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_ones', \%in, %a);
}

sub _plus {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_plus', \%in, %a);
}

sub _plus_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_plus_scalar', \%in, %a);
}

sub _power {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_power', \%in, %a);
}

sub _power_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_power_scalar', \%in, %a);
}

sub _random_exponential {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_exponential', \%in, %a);
}

sub _random_gamma {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_gamma', \%in, %a);
}

sub _random_generalized_negative_binomial {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_generalized_negative_binomial', \%in, %a);
}

sub _random_negative_binomial {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_negative_binomial', \%in, %a);
}

sub _random_normal {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_normal', \%in, %a);
}

sub _random_poisson {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_poisson', \%in, %a);
}

sub _random_uniform {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_random_uniform', \%in, %a);
}

sub _rdiv_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_rdiv_scalar', \%in, %a);
}

sub _rminus_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_rminus_scalar', \%in, %a);
}

sub _rmod_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_rmod_scalar', \%in, %a);
}

sub _rpower_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_rpower_scalar', \%in, %a);
}

sub _slice_assign {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_slice_assign', \%in, %a);
}

sub _slice_assign_scalar {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_slice_assign_scalar', \%in, %a);
}

sub _square_sum {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('_square_sum', \%in, %a);
}

sub _sub {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('_sub', \%in, %a);
}

sub _sum {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('_sum', $ins, %a);
}

sub _zeros {
    my %a = @_;
    my %in;
    return AI::MXTPU::Symbol->create('_zeros', \%in, %a);
}

sub abs {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('abs', \%in, %a);
}

sub adam_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'mean'} = delete $a{'mean'} if exists $a{'mean'};
    $in{'var'} = delete $a{'var'} if exists $a{'var'};
    return AI::MXTPU::Symbol->create('adam_update', \%in, %a);
}

sub add_n {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('add_n', $ins, %a);
}

sub arccos {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('arccos', \%in, %a);
}

sub arccosh {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('arccosh', \%in, %a);
}

sub arcsin {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('arcsin', \%in, %a);
}

sub arcsinh {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('arcsinh', \%in, %a);
}

sub arctan {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('arctan', \%in, %a);
}

sub arctanh {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('arctanh', \%in, %a);
}

sub argmax {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('argmax', \%in, %a);
}

sub argmax_channel {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('argmax_channel', \%in, %a);
}

sub argmin {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('argmin', \%in, %a);
}

sub argsort {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('argsort', \%in, %a);
}

sub batch_dot {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('batch_dot', \%in, %a);
}

sub batch_take {
    my %a = @_;
    my %in;
    $in{'a'} = delete $a{'a'} if exists $a{'a'};
    $in{'indices'} = delete $a{'indices'} if exists $a{'indices'};
    return AI::MXTPU::Symbol->create('batch_take', \%in, %a);
}

sub broadcast_add {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_add', \%in, %a);
}

sub broadcast_axes {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('broadcast_axes', \%in, %a);
}

sub broadcast_axis {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('broadcast_axis', \%in, %a);
}

sub broadcast_div {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_div', \%in, %a);
}

sub broadcast_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_equal', \%in, %a);
}

sub broadcast_greater {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_greater', \%in, %a);
}

sub broadcast_greater_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_greater_equal', \%in, %a);
}

sub broadcast_hypot {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_hypot', \%in, %a);
}

sub broadcast_lesser {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_lesser', \%in, %a);
}

sub broadcast_lesser_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_lesser_equal', \%in, %a);
}

sub broadcast_maximum {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_maximum', \%in, %a);
}

sub broadcast_minimum {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_minimum', \%in, %a);
}

sub broadcast_minus {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_minus', \%in, %a);
}

sub broadcast_mod {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_mod', \%in, %a);
}

sub broadcast_mul {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_mul', \%in, %a);
}

sub broadcast_not_equal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_not_equal', \%in, %a);
}

sub broadcast_plus {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_plus', \%in, %a);
}

sub broadcast_power {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_power', \%in, %a);
}

sub broadcast_sub {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('broadcast_sub', \%in, %a);
}

sub broadcast_to {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('broadcast_to', \%in, %a);
}

sub cast {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('cast', \%in, %a);
}

sub cast_storage {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('cast_storage', \%in, %a);
}

sub causal_conv1d {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('causal_conv1d', \%in, %a);
}

sub cbrt {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('cbrt', \%in, %a);
}

sub ceil {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('ceil', \%in, %a);
}

sub clip {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('clip', \%in, %a);
}

sub concat {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('concat', $ins, %a);
}

sub cos {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('cos', \%in, %a);
}

sub cosh {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('cosh', \%in, %a);
}

sub crop {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('crop', \%in, %a);
}

sub ctc_loss {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('ctc_loss', \%in, %a);
}

sub degrees {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('degrees', \%in, %a);
}

sub dequantize_int8 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('dequantize_int8', \%in, %a);
}

sub dot {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('dot', \%in, %a);
}

sub elemwise_add {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('elemwise_add', \%in, %a);
}

sub elemwise_div {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('elemwise_div', \%in, %a);
}

sub elemwise_mul {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('elemwise_mul', \%in, %a);
}

sub elemwise_sub {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('elemwise_sub', \%in, %a);
}

sub erf {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('erf', \%in, %a);
}

sub exp {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('exp', \%in, %a);
}

sub expand_dims {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('expand_dims', \%in, %a);
}

sub expm1 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('expm1', \%in, %a);
}

sub fix {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('fix', \%in, %a);
}

sub flash_attention {
    my %a = @_;
    my %in;
    $in{'query'} = delete $a{'query'} if exists $a{'query'};
    $in{'key'} = delete $a{'key'} if exists $a{'key'};
    $in{'value'} = delete $a{'value'} if exists $a{'value'};
    return AI::MXTPU::Symbol->create('flash_attention', \%in, %a);
}

sub flatten {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('flatten', \%in, %a);
}

sub flip {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('flip', \%in, %a);
}

sub floor {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('floor', \%in, %a);
}

sub ftrl_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'z'} = delete $a{'z'} if exists $a{'z'};
    $in{'n'} = delete $a{'n'} if exists $a{'n'};
    return AI::MXTPU::Symbol->create('ftrl_update', \%in, %a);
}

sub gamma {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('gamma', \%in, %a);
}

sub gammaln {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('gammaln', \%in, %a);
}

sub gated_delta_rule {
    my %a = @_;
    my %in;
    $in{'query'} = delete $a{'query'} if exists $a{'query'};
    $in{'key'} = delete $a{'key'} if exists $a{'key'};
    $in{'value'} = delete $a{'value'} if exists $a{'value'};
    $in{'g'} = delete $a{'g'} if exists $a{'g'};
    $in{'beta'} = delete $a{'beta'} if exists $a{'beta'};
    return AI::MXTPU::Symbol->create('gated_delta_rule', \%in, %a);
}

sub gather_nd {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'indices'} = delete $a{'indices'} if exists $a{'indices'};
    return AI::MXTPU::Symbol->create('gather_nd', \%in, %a);
}

sub head_gate {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gate'} = delete $a{'gate'} if exists $a{'gate'};
    return AI::MXTPU::Symbol->create('head_gate', \%in, %a);
}

sub head_norm_rotary {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'gamma'} = delete $a{'gamma'} if exists $a{'gamma'};
    return AI::MXTPU::Symbol->create('head_norm_rotary', \%in, %a);
}

sub identity {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('identity', \%in, %a);
}

sub khatri_rao {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('khatri_rao', $ins, %a);
}

sub linalg_gelqf {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('linalg_gelqf', \%in, %a);
}

sub linalg_gemm {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    $in{'C'} = delete $a{'C'} if exists $a{'C'};
    return AI::MXTPU::Symbol->create('linalg_gemm', \%in, %a);
}

sub linalg_gemm2 {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    return AI::MXTPU::Symbol->create('linalg_gemm2', \%in, %a);
}

sub linalg_potrf {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('linalg_potrf', \%in, %a);
}

sub linalg_potri {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('linalg_potri', \%in, %a);
}

sub linalg_sumlogdiag {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('linalg_sumlogdiag', \%in, %a);
}

sub linalg_syrk {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    return AI::MXTPU::Symbol->create('linalg_syrk', \%in, %a);
}

sub linalg_trmm {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    return AI::MXTPU::Symbol->create('linalg_trmm', \%in, %a);
}

sub linalg_trsm {
    my %a = @_;
    my %in;
    $in{'A'} = delete $a{'A'} if exists $a{'A'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    return AI::MXTPU::Symbol->create('linalg_trsm', \%in, %a);
}

sub log {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('log', \%in, %a);
}

sub log10 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('log10', \%in, %a);
}

sub log1p {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('log1p', \%in, %a);
}

sub log2 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('log2', \%in, %a);
}

sub log_softmax {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('log_softmax', \%in, %a);
}

sub make_loss {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('make_loss', \%in, %a);
}

sub max {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('max', \%in, %a);
}

sub mean {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('mean', \%in, %a);
}

sub min {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('min', \%in, %a);
}

sub moe_experts {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'topk_weight'} = delete $a{'topk_weight'} if exists $a{'topk_weight'};
    $in{'topk_index'} = delete $a{'topk_index'} if exists $a{'topk_index'};
    $in{'gate_weight'} = delete $a{'gate_weight'} if exists $a{'gate_weight'};
    $in{'up_weight'} = delete $a{'up_weight'} if exists $a{'up_weight'};
    $in{'down_weight'} = delete $a{'down_weight'} if exists $a{'down_weight'};
    return AI::MXTPU::Symbol->create('moe_experts', \%in, %a);
}

sub moe_router {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    return AI::MXTPU::Symbol->create('moe_router', \%in, %a);
}

sub mp_sgd_mom_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'mom'} = delete $a{'mom'} if exists $a{'mom'};
    $in{'weight32'} = delete $a{'weight32'} if exists $a{'weight32'};
    return AI::MXTPU::Symbol->create('mp_sgd_mom_update', \%in, %a);
}

sub mp_sgd_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'weight32'} = delete $a{'weight32'} if exists $a{'weight32'};
    return AI::MXTPU::Symbol->create('mp_sgd_update', \%in, %a);
}

sub nanprod {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('nanprod', \%in, %a);
}

sub nansum {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('nansum', \%in, %a);
}

sub negative {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('negative', \%in, %a);
}

sub norm {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('norm', \%in, %a);
}

sub one_hot {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('one_hot', \%in, %a);
}

sub ones_like {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('ones_like', \%in, %a);
}

sub pad {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('pad', \%in, %a);
}

sub pick {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'index'} = delete $a{'index'} if exists $a{'index'};
    return AI::MXTPU::Symbol->create('pick', \%in, %a);
}

sub prod {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('prod', \%in, %a);
}

sub quantize_int8 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('quantize_int8', \%in, %a);
}

sub radians {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('radians', \%in, %a);
}

sub rcbrt {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('rcbrt', \%in, %a);
}

sub reciprocal {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('reciprocal', \%in, %a);
}

sub relu {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('relu', \%in, %a);
}

sub repeat {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('repeat', \%in, %a);
}

sub reshape {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('reshape', \%in, %a);
}

sub reshape_like {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('reshape_like', \%in, %a);
}

sub reverse {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('reverse', \%in, %a);
}

sub rint {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('rint', \%in, %a);
}

sub rmsprop_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'n'} = delete $a{'n'} if exists $a{'n'};
    return AI::MXTPU::Symbol->create('rmsprop_update', \%in, %a);
}

sub rmspropalex_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'n'} = delete $a{'n'} if exists $a{'n'};
    $in{'g'} = delete $a{'g'} if exists $a{'g'};
    $in{'delta'} = delete $a{'delta'} if exists $a{'delta'};
    return AI::MXTPU::Symbol->create('rmspropalex_update', \%in, %a);
}

sub rotary_embedding {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('rotary_embedding', \%in, %a);
}

sub round {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('round', \%in, %a);
}

sub rsqrt {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('rsqrt', \%in, %a);
}

sub sample_exponential {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sample_exponential', \%in, %a);
}

sub sample_gamma {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('sample_gamma', \%in, %a);
}

sub sample_generalized_negative_binomial {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('sample_generalized_negative_binomial', \%in, %a);
}

sub sample_multinomial {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sample_multinomial', \%in, %a);
}

sub sample_negative_binomial {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('sample_negative_binomial', \%in, %a);
}

sub sample_normal {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('sample_normal', \%in, %a);
}

sub sample_poisson {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sample_poisson', \%in, %a);
}

sub sample_uniform {
    my %a = @_;
    my %in;
    $in{'lhs'} = delete $a{'lhs'} if exists $a{'lhs'};
    $in{'rhs'} = delete $a{'rhs'} if exists $a{'rhs'};
    return AI::MXTPU::Symbol->create('sample_uniform', \%in, %a);
}

sub scatter_nd {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'indices'} = delete $a{'indices'} if exists $a{'indices'};
    return AI::MXTPU::Symbol->create('scatter_nd', \%in, %a);
}

sub sgd_mom_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    $in{'mom'} = delete $a{'mom'} if exists $a{'mom'};
    return AI::MXTPU::Symbol->create('sgd_mom_update', \%in, %a);
}

sub sgd_update {
    my %a = @_;
    my %in;
    $in{'weight'} = delete $a{'weight'} if exists $a{'weight'};
    $in{'grad'} = delete $a{'grad'} if exists $a{'grad'};
    return AI::MXTPU::Symbol->create('sgd_update', \%in, %a);
}

sub sigmoid {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sigmoid', \%in, %a);
}

sub sign {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sign', \%in, %a);
}

sub sin {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sin', \%in, %a);
}

sub sinh {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sinh', \%in, %a);
}

sub slice {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('slice', \%in, %a);
}

sub slice_axis {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('slice_axis', \%in, %a);
}

sub smooth_l1 {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('smooth_l1', \%in, %a);
}

sub softmax {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('softmax', \%in, %a);
}

sub softmax_cross_entropy {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'label'} = delete $a{'label'} if exists $a{'label'};
    return AI::MXTPU::Symbol->create('softmax_cross_entropy', \%in, %a);
}

sub softsign {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('softsign', \%in, %a);
}

sub sort {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sort', \%in, %a);
}

sub space_to_depth {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('space_to_depth', \%in, %a);
}

sub split {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('split', \%in, %a);
}

sub sqrt {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sqrt', \%in, %a);
}

sub square {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('square', \%in, %a);
}

sub ssd_scan {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    $in{'dt'} = delete $a{'dt'} if exists $a{'dt'};
    $in{'A_log'} = delete $a{'A_log'} if exists $a{'A_log'};
    $in{'B'} = delete $a{'B'} if exists $a{'B'};
    $in{'C'} = delete $a{'C'} if exists $a{'C'};
    $in{'D'} = delete $a{'D'} if exists $a{'D'};
    return AI::MXTPU::Symbol->create('ssd_scan', \%in, %a);
}

sub stack {
    my %a = @_;
    my %in;
    my $ins = delete $a{inputs} || [];
    return AI::MXTPU::Symbol->create('stack', $ins, %a);
}

sub stop_gradient {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('stop_gradient', \%in, %a);
}

sub sum {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sum', \%in, %a);
}

sub sum_axis {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('sum_axis', \%in, %a);
}

sub swapaxes {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('swapaxes', \%in, %a);
}

sub take {
    my %a = @_;
    my %in;
    $in{'a'} = delete $a{'a'} if exists $a{'a'};
    $in{'indices'} = delete $a{'indices'} if exists $a{'indices'};
    return AI::MXTPU::Symbol->create('take', \%in, %a);
}

sub tan {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('tan', \%in, %a);
}

sub tanh {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('tanh', \%in, %a);
}

sub tile {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('tile', \%in, %a);
}

sub topk {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('topk', \%in, %a);
}

sub transpose {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('transpose', \%in, %a);
}

sub trunc {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('trunc', \%in, %a);
}

sub where {
    my %a = @_;
    my %in;
    $in{'condition'} = delete $a{'condition'} if exists $a{'condition'};
    $in{'x'} = delete $a{'x'} if exists $a{'x'};
    $in{'y'} = delete $a{'y'} if exists $a{'y'};
    return AI::MXTPU::Symbol->create('where', \%in, %a);
}

sub zeros_like {
    my %a = @_;
    my %in;
    $in{'data'} = delete $a{'data'} if exists $a{'data'};
    return AI::MXTPU::Symbol->create('zeros_like', \%in, %a);
}

1;
