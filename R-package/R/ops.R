# GENERATED by R-package/gen_r_ops.py -- do not edit.
# One mx.symbol.<op> function per registered op (307 ops); tensor inputs
# are mx.symbol objects passed by name, everything else becomes a string
# param (the reference R package generates its op surface the same way).

`mx.symbol.Activation` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Activation", a[is.sym], a[!is.sym], name)
}

`mx.symbol.BatchNorm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("BatchNorm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.BatchNorm_v1` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("BatchNorm_v1", a[is.sym], a[!is.sym], name)
}

`mx.symbol.BilinearSampler` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("BilinearSampler", a[is.sym], a[!is.sym], name)
}

`mx.symbol.BlockGrad` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("BlockGrad", a[is.sym], a[!is.sym], name)
}

`mx.symbol.CTCLoss` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("CTCLoss", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Cast` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Cast", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Concat` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Concat", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Convolution` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Convolution", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Convolution_v1` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Convolution_v1", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Correlation` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Correlation", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Crop` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Crop", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Custom` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Custom", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Deconvolution` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Deconvolution", a[is.sym], a[!is.sym], name)
}

`mx.symbol.DeformableConvolution` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("DeformableConvolution", a[is.sym], a[!is.sym], name)
}

`mx.symbol.DeformablePSROIPooling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("DeformablePSROIPooling", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Dropout` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Dropout", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ElementWiseSum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ElementWiseSum", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Embedding` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Embedding", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Flatten` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Flatten", a[is.sym], a[!is.sym], name)
}

`mx.symbol.FullyConnected` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("FullyConnected", a[is.sym], a[!is.sym], name)
}

`mx.symbol.GridGenerator` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("GridGenerator", a[is.sym], a[!is.sym], name)
}

`mx.symbol.IdentityAttachKLSparseReg` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("IdentityAttachKLSparseReg", a[is.sym], a[!is.sym], name)
}

`mx.symbol.InstanceNorm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("InstanceNorm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.L2Normalization` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("L2Normalization", a[is.sym], a[!is.sym], name)
}

`mx.symbol.LRN` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("LRN", a[is.sym], a[!is.sym], name)
}

`mx.symbol.LayerNorm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("LayerNorm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.LeakyReLU` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("LeakyReLU", a[is.sym], a[!is.sym], name)
}

`mx.symbol.LinearRegressionOutput` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("LinearRegressionOutput", a[is.sym], a[!is.sym], name)
}

`mx.symbol.LogisticRegressionOutput` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("LogisticRegressionOutput", a[is.sym], a[!is.sym], name)
}

`mx.symbol.MAERegressionOutput` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("MAERegressionOutput", a[is.sym], a[!is.sym], name)
}

`mx.symbol.MakeLoss` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("MakeLoss", a[is.sym], a[!is.sym], name)
}

`mx.symbol.MultiBoxDetection` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("MultiBoxDetection", a[is.sym], a[!is.sym], name)
}

`mx.symbol.MultiBoxPrior` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("MultiBoxPrior", a[is.sym], a[!is.sym], name)
}

`mx.symbol.MultiBoxTarget` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("MultiBoxTarget", a[is.sym], a[!is.sym], name)
}

`mx.symbol.MultiProposal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("MultiProposal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.PSROIPooling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("PSROIPooling", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Pad` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Pad", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Pooling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Pooling", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Pooling_v1` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Pooling_v1", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Proposal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Proposal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.RMSNorm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("RMSNorm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.RNN` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("RNN", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ROIPooling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ROIPooling", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Reshape` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Reshape", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SVMOutput` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SVMOutput", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SequenceLast` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SequenceLast", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SequenceMask` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SequenceMask", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SequenceReverse` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SequenceReverse", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SliceChannel` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SliceChannel", a[is.sym], a[!is.sym], name)
}

`mx.symbol.Softmax` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("Softmax", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SoftmaxActivation` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SoftmaxActivation", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SoftmaxOutput` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SoftmaxOutput", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SpatialTransformer` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SpatialTransformer", a[is.sym], a[!is.sym], name)
}

`mx.symbol.SwapAxis` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("SwapAxis", a[is.sym], a[!is.sym], name)
}

`mx.symbol.UpSampling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("UpSampling", a[is.sym], a[!is.sym], name)
}

`mx.symbol._Custom` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_Custom", a[is.sym], a[!is.sym], name)
}

`mx.symbol._NoGradient` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_NoGradient", a[is.sym], a[!is.sym], name)
}

`mx.symbol._add` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_add", a[is.sym], a[!is.sym], name)
}

`mx.symbol._arange` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_arange", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_CTCLoss` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_CTCLoss", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_CausalConv1D` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_CausalConv1D", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_DeformableConvolution` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_DeformableConvolution", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_DeformablePSROIPooling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_DeformablePSROIPooling", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_FlashAttention` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_FlashAttention", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_GatedDeltaRule` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_GatedDeltaRule", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_HeadGate` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_HeadGate", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_HeadNormRotary` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_HeadNormRotary", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_MoEExperts` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_MoEExperts", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_MoERouter` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_MoERouter", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_MultiBoxDetection` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_MultiBoxDetection", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_MultiBoxPrior` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_MultiBoxPrior", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_MultiBoxTarget` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_MultiBoxTarget", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_MultiProposal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_MultiProposal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_PSROIPooling` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_PSROIPooling", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_Proposal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_Proposal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_RotaryEmbedding` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_RotaryEmbedding", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_SSDScan` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_SSDScan", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_count_sketch` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_count_sketch", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_ctc_loss` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_ctc_loss", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_dequantize` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_dequantize", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_fft` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_fft", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_ifft` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_ifft", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_krprod` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_krprod", a[is.sym], a[!is.sym], name)
}

`mx.symbol._contrib_quantize` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_contrib_quantize", a[is.sym], a[!is.sym], name)
}

`mx.symbol._copy` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_copy", a[is.sym], a[!is.sym], name)
}

`mx.symbol._crop_assign` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_crop_assign", a[is.sym], a[!is.sym], name)
}

`mx.symbol._crop_assign_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_crop_assign_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._div` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_div", a[is.sym], a[!is.sym], name)
}

`mx.symbol._div_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_div_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._equal_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_equal_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._full` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_full", a[is.sym], a[!is.sym], name)
}

`mx.symbol._grad_add` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_grad_add", a[is.sym], a[!is.sym], name)
}

`mx.symbol._greater` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_greater", a[is.sym], a[!is.sym], name)
}

`mx.symbol._greater_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_greater_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._greater_equal_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_greater_equal_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._greater_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_greater_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._hypot` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_hypot", a[is.sym], a[!is.sym], name)
}

`mx.symbol._hypot_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_hypot_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._identity_with_attr_like_rhs` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_identity_with_attr_like_rhs", a[is.sym], a[!is.sym], name)
}

`mx.symbol._khatri_rao` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_khatri_rao", a[is.sym], a[!is.sym], name)
}

`mx.symbol._lesser` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_lesser", a[is.sym], a[!is.sym], name)
}

`mx.symbol._lesser_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_lesser_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._lesser_equal_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_lesser_equal_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._lesser_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_lesser_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_gelqf` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_gelqf", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_gemm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_gemm", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_gemm2` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_gemm2", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_potrf` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_potrf", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_potri` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_potri", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_sumlogdiag` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_sumlogdiag", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_syrk` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_syrk", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_trmm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_trmm", a[is.sym], a[!is.sym], name)
}

`mx.symbol._linalg_trsm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_linalg_trsm", a[is.sym], a[!is.sym], name)
}

`mx.symbol._maximum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_maximum", a[is.sym], a[!is.sym], name)
}

`mx.symbol._maximum_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_maximum_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._minimum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_minimum", a[is.sym], a[!is.sym], name)
}

`mx.symbol._minimum_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_minimum_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._minus` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_minus", a[is.sym], a[!is.sym], name)
}

`mx.symbol._minus_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_minus_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._mod` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_mod", a[is.sym], a[!is.sym], name)
}

`mx.symbol._mod_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_mod_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._mul` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_mul", a[is.sym], a[!is.sym], name)
}

`mx.symbol._mul_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_mul_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._not_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_not_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._not_equal_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_not_equal_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._ones` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_ones", a[is.sym], a[!is.sym], name)
}

`mx.symbol._plus` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_plus", a[is.sym], a[!is.sym], name)
}

`mx.symbol._plus_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_plus_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._power` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_power", a[is.sym], a[!is.sym], name)
}

`mx.symbol._power_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_power_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_exponential` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_exponential", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_gamma` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_gamma", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_generalized_negative_binomial` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_generalized_negative_binomial", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_negative_binomial` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_negative_binomial", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_normal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_normal", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_poisson` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_poisson", a[is.sym], a[!is.sym], name)
}

`mx.symbol._random_uniform` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_random_uniform", a[is.sym], a[!is.sym], name)
}

`mx.symbol._rdiv_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_rdiv_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._rminus_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_rminus_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._rmod_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_rmod_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._rpower_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_rpower_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._slice_assign` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_slice_assign", a[is.sym], a[!is.sym], name)
}

`mx.symbol._slice_assign_scalar` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_slice_assign_scalar", a[is.sym], a[!is.sym], name)
}

`mx.symbol._square_sum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_square_sum", a[is.sym], a[!is.sym], name)
}

`mx.symbol._sub` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_sub", a[is.sym], a[!is.sym], name)
}

`mx.symbol._sum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_sum", a[is.sym], a[!is.sym], name)
}

`mx.symbol._zeros` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("_zeros", a[is.sym], a[!is.sym], name)
}

`mx.symbol.abs` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("abs", a[is.sym], a[!is.sym], name)
}

`mx.symbol.adam_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("adam_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.add_n` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("add_n", a[is.sym], a[!is.sym], name)
}

`mx.symbol.arccos` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("arccos", a[is.sym], a[!is.sym], name)
}

`mx.symbol.arccosh` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("arccosh", a[is.sym], a[!is.sym], name)
}

`mx.symbol.arcsin` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("arcsin", a[is.sym], a[!is.sym], name)
}

`mx.symbol.arcsinh` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("arcsinh", a[is.sym], a[!is.sym], name)
}

`mx.symbol.arctan` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("arctan", a[is.sym], a[!is.sym], name)
}

`mx.symbol.arctanh` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("arctanh", a[is.sym], a[!is.sym], name)
}

`mx.symbol.argmax` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("argmax", a[is.sym], a[!is.sym], name)
}

`mx.symbol.argmax_channel` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("argmax_channel", a[is.sym], a[!is.sym], name)
}

`mx.symbol.argmin` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("argmin", a[is.sym], a[!is.sym], name)
}

`mx.symbol.argsort` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("argsort", a[is.sym], a[!is.sym], name)
}

`mx.symbol.batch_dot` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("batch_dot", a[is.sym], a[!is.sym], name)
}

`mx.symbol.batch_take` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("batch_take", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_add` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_add", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_axes` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_axes", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_axis` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_axis", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_div` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_div", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_greater` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_greater", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_greater_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_greater_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_hypot` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_hypot", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_lesser` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_lesser", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_lesser_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_lesser_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_maximum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_maximum", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_minimum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_minimum", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_minus` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_minus", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_mod` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_mod", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_mul` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_mul", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_not_equal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_not_equal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_plus` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_plus", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_power` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_power", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_sub` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_sub", a[is.sym], a[!is.sym], name)
}

`mx.symbol.broadcast_to` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("broadcast_to", a[is.sym], a[!is.sym], name)
}

`mx.symbol.cast` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("cast", a[is.sym], a[!is.sym], name)
}

`mx.symbol.cast_storage` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("cast_storage", a[is.sym], a[!is.sym], name)
}

`mx.symbol.causal_conv1d` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("causal_conv1d", a[is.sym], a[!is.sym], name)
}

`mx.symbol.cbrt` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("cbrt", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ceil` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ceil", a[is.sym], a[!is.sym], name)
}

`mx.symbol.clip` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("clip", a[is.sym], a[!is.sym], name)
}

`mx.symbol.concat` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("concat", a[is.sym], a[!is.sym], name)
}

`mx.symbol.cos` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("cos", a[is.sym], a[!is.sym], name)
}

`mx.symbol.cosh` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("cosh", a[is.sym], a[!is.sym], name)
}

`mx.symbol.crop` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("crop", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ctc_loss` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ctc_loss", a[is.sym], a[!is.sym], name)
}

`mx.symbol.degrees` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("degrees", a[is.sym], a[!is.sym], name)
}

`mx.symbol.dequantize_int8` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("dequantize_int8", a[is.sym], a[!is.sym], name)
}

`mx.symbol.dot` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("dot", a[is.sym], a[!is.sym], name)
}

`mx.symbol.elemwise_add` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("elemwise_add", a[is.sym], a[!is.sym], name)
}

`mx.symbol.elemwise_div` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("elemwise_div", a[is.sym], a[!is.sym], name)
}

`mx.symbol.elemwise_mul` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("elemwise_mul", a[is.sym], a[!is.sym], name)
}

`mx.symbol.elemwise_sub` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("elemwise_sub", a[is.sym], a[!is.sym], name)
}

`mx.symbol.erf` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("erf", a[is.sym], a[!is.sym], name)
}

`mx.symbol.exp` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("exp", a[is.sym], a[!is.sym], name)
}

`mx.symbol.expand_dims` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("expand_dims", a[is.sym], a[!is.sym], name)
}

`mx.symbol.expm1` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("expm1", a[is.sym], a[!is.sym], name)
}

`mx.symbol.fix` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("fix", a[is.sym], a[!is.sym], name)
}

`mx.symbol.flash_attention` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("flash_attention", a[is.sym], a[!is.sym], name)
}

`mx.symbol.flatten` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("flatten", a[is.sym], a[!is.sym], name)
}

`mx.symbol.flip` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("flip", a[is.sym], a[!is.sym], name)
}

`mx.symbol.floor` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("floor", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ftrl_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ftrl_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.gamma` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("gamma", a[is.sym], a[!is.sym], name)
}

`mx.symbol.gammaln` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("gammaln", a[is.sym], a[!is.sym], name)
}

`mx.symbol.gated_delta_rule` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("gated_delta_rule", a[is.sym], a[!is.sym], name)
}

`mx.symbol.gather_nd` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("gather_nd", a[is.sym], a[!is.sym], name)
}

`mx.symbol.head_gate` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("head_gate", a[is.sym], a[!is.sym], name)
}

`mx.symbol.head_norm_rotary` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("head_norm_rotary", a[is.sym], a[!is.sym], name)
}

`mx.symbol.identity` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("identity", a[is.sym], a[!is.sym], name)
}

`mx.symbol.khatri_rao` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("khatri_rao", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_gelqf` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_gelqf", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_gemm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_gemm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_gemm2` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_gemm2", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_potrf` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_potrf", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_potri` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_potri", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_sumlogdiag` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_sumlogdiag", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_syrk` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_syrk", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_trmm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_trmm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.linalg_trsm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("linalg_trsm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.log` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("log", a[is.sym], a[!is.sym], name)
}

`mx.symbol.log10` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("log10", a[is.sym], a[!is.sym], name)
}

`mx.symbol.log1p` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("log1p", a[is.sym], a[!is.sym], name)
}

`mx.symbol.log2` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("log2", a[is.sym], a[!is.sym], name)
}

`mx.symbol.log_softmax` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("log_softmax", a[is.sym], a[!is.sym], name)
}

`mx.symbol.make_loss` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("make_loss", a[is.sym], a[!is.sym], name)
}

`mx.symbol.max` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("max", a[is.sym], a[!is.sym], name)
}

`mx.symbol.mean` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("mean", a[is.sym], a[!is.sym], name)
}

`mx.symbol.min` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("min", a[is.sym], a[!is.sym], name)
}

`mx.symbol.moe_experts` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("moe_experts", a[is.sym], a[!is.sym], name)
}

`mx.symbol.moe_router` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("moe_router", a[is.sym], a[!is.sym], name)
}

`mx.symbol.mp_sgd_mom_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("mp_sgd_mom_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.mp_sgd_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("mp_sgd_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.nanprod` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("nanprod", a[is.sym], a[!is.sym], name)
}

`mx.symbol.nansum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("nansum", a[is.sym], a[!is.sym], name)
}

`mx.symbol.negative` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("negative", a[is.sym], a[!is.sym], name)
}

`mx.symbol.norm` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("norm", a[is.sym], a[!is.sym], name)
}

`mx.symbol.one_hot` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("one_hot", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ones_like` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ones_like", a[is.sym], a[!is.sym], name)
}

`mx.symbol.pad` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("pad", a[is.sym], a[!is.sym], name)
}

`mx.symbol.pick` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("pick", a[is.sym], a[!is.sym], name)
}

`mx.symbol.prod` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("prod", a[is.sym], a[!is.sym], name)
}

`mx.symbol.quantize_int8` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("quantize_int8", a[is.sym], a[!is.sym], name)
}

`mx.symbol.radians` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("radians", a[is.sym], a[!is.sym], name)
}

`mx.symbol.rcbrt` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("rcbrt", a[is.sym], a[!is.sym], name)
}

`mx.symbol.reciprocal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("reciprocal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.relu` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("relu", a[is.sym], a[!is.sym], name)
}

`mx.symbol.repeat` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("repeat", a[is.sym], a[!is.sym], name)
}

`mx.symbol.reshape` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("reshape", a[is.sym], a[!is.sym], name)
}

`mx.symbol.reshape_like` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("reshape_like", a[is.sym], a[!is.sym], name)
}

`mx.symbol.reverse` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("reverse", a[is.sym], a[!is.sym], name)
}

`mx.symbol.rint` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("rint", a[is.sym], a[!is.sym], name)
}

`mx.symbol.rmsprop_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("rmsprop_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.rmspropalex_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("rmspropalex_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.rotary_embedding` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("rotary_embedding", a[is.sym], a[!is.sym], name)
}

`mx.symbol.round` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("round", a[is.sym], a[!is.sym], name)
}

`mx.symbol.rsqrt` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("rsqrt", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_exponential` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_exponential", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_gamma` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_gamma", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_generalized_negative_binomial` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_generalized_negative_binomial", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_multinomial` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_multinomial", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_negative_binomial` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_negative_binomial", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_normal` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_normal", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_poisson` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_poisson", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sample_uniform` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sample_uniform", a[is.sym], a[!is.sym], name)
}

`mx.symbol.scatter_nd` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("scatter_nd", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sgd_mom_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sgd_mom_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sgd_update` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sgd_update", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sigmoid` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sigmoid", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sign` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sign", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sin` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sin", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sinh` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sinh", a[is.sym], a[!is.sym], name)
}

`mx.symbol.slice` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("slice", a[is.sym], a[!is.sym], name)
}

`mx.symbol.slice_axis` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("slice_axis", a[is.sym], a[!is.sym], name)
}

`mx.symbol.smooth_l1` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("smooth_l1", a[is.sym], a[!is.sym], name)
}

`mx.symbol.softmax` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("softmax", a[is.sym], a[!is.sym], name)
}

`mx.symbol.softmax_cross_entropy` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("softmax_cross_entropy", a[is.sym], a[!is.sym], name)
}

`mx.symbol.softsign` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("softsign", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sort` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sort", a[is.sym], a[!is.sym], name)
}

`mx.symbol.space_to_depth` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("space_to_depth", a[is.sym], a[!is.sym], name)
}

`mx.symbol.split` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("split", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sqrt` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sqrt", a[is.sym], a[!is.sym], name)
}

`mx.symbol.square` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("square", a[is.sym], a[!is.sym], name)
}

`mx.symbol.ssd_scan` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("ssd_scan", a[is.sym], a[!is.sym], name)
}

`mx.symbol.stack` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("stack", a[is.sym], a[!is.sym], name)
}

`mx.symbol.stop_gradient` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("stop_gradient", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sum` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sum", a[is.sym], a[!is.sym], name)
}

`mx.symbol.sum_axis` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("sum_axis", a[is.sym], a[!is.sym], name)
}

`mx.symbol.swapaxes` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("swapaxes", a[is.sym], a[!is.sym], name)
}

`mx.symbol.take` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("take", a[is.sym], a[!is.sym], name)
}

`mx.symbol.tan` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("tan", a[is.sym], a[!is.sym], name)
}

`mx.symbol.tanh` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("tanh", a[is.sym], a[!is.sym], name)
}

`mx.symbol.tile` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("tile", a[is.sym], a[!is.sym], name)
}

`mx.symbol.topk` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("topk", a[is.sym], a[!is.sym], name)
}

`mx.symbol.transpose` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("transpose", a[is.sym], a[!is.sym], name)
}

`mx.symbol.trunc` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("trunc", a[is.sym], a[!is.sym], name)
}

`mx.symbol.where` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("where", a[is.sym], a[!is.sym], name)
}

`mx.symbol.zeros_like` <- function(..., name = "") {
  a <- list(...)
  is.sym <- vapply(a, function(v) inherits(v, "mx.symbol"), TRUE)
  mx.symbol.create("zeros_like", a[is.sym], a[!is.sym], name)
}

