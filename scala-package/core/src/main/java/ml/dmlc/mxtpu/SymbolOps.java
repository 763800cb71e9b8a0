package ml.dmlc.mxtpu;

// GENERATED by scala-package/gen_jvm_ops.py - do not edit.
// One static method per registered op (307 ops). Regenerate after
// registry changes. Parity: the reference scala-package's generated op
// surface (scala-package/macros SymbolMacro/NDArrayMacro over
// MXListAllOpNames).

public final class SymbolOps {
  private SymbolOps() {}

  /** Activation(data) */
  public static Symbol Activation(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Activation", name, attrs, new String[] {"data"}, inputs);
  }

  /** BatchNorm(data, gamma, beta, moving_mean, moving_var) */
  public static Symbol BatchNorm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("BatchNorm", name, attrs, new String[] {"data", "gamma", "beta", "moving_mean", "moving_var"}, inputs);
  }

  /** BatchNorm_v1(data, gamma, beta, moving_mean, moving_var) */
  public static Symbol BatchNorm_v1(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("BatchNorm_v1", name, attrs, new String[] {"data", "gamma", "beta", "moving_mean", "moving_var"}, inputs);
  }

  /** BilinearSampler(data, grid) */
  public static Symbol BilinearSampler(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("BilinearSampler", name, attrs, new String[] {"data", "grid"}, inputs);
  }

  /** BlockGrad(data) */
  public static Symbol BlockGrad(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("BlockGrad", name, attrs, new String[] {"data"}, inputs);
  }

  /** CTCLoss(data, label) */
  public static Symbol CTCLoss(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("CTCLoss", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** Cast(data) */
  public static Symbol Cast(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Cast", name, attrs, new String[] {"data"}, inputs);
  }

  /** Concat(variadic) */
  public static Symbol Concat(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Concat", name, attrs, null, inputs);
  }

  /** Convolution(data, weight, bias) */
  public static Symbol Convolution(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Convolution", name, attrs, new String[] {"data", "weight", "bias"}, inputs);
  }

  /** Convolution_v1(data, weight, bias) */
  public static Symbol Convolution_v1(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Convolution_v1", name, attrs, new String[] {"data", "weight", "bias"}, inputs);
  }

  /** Correlation(data1, data2) */
  public static Symbol Correlation(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Correlation", name, attrs, new String[] {"data1", "data2"}, inputs);
  }

  /** Crop(variadic) */
  public static Symbol Crop(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Crop", name, attrs, null, inputs);
  }

  /** Custom(variadic) */
  public static Symbol Custom(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Custom", name, attrs, null, inputs);
  }

  /** Deconvolution(data, weight) */
  public static Symbol Deconvolution(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Deconvolution", name, attrs, new String[] {"data", "weight"}, inputs);
  }

  /** DeformableConvolution(data, offset, weight) */
  public static Symbol DeformableConvolution(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("DeformableConvolution", name, attrs, new String[] {"data", "offset", "weight"}, inputs);
  }

  /** DeformablePSROIPooling(data, rois, trans) */
  public static Symbol DeformablePSROIPooling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("DeformablePSROIPooling", name, attrs, new String[] {"data", "rois", "trans"}, inputs);
  }

  /** Dropout(data) */
  public static Symbol Dropout(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Dropout", name, attrs, new String[] {"data"}, inputs);
  }

  /** ElementWiseSum(variadic) */
  public static Symbol ElementWiseSum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ElementWiseSum", name, attrs, null, inputs);
  }

  /** Embedding(data, weight) */
  public static Symbol Embedding(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Embedding", name, attrs, new String[] {"data", "weight"}, inputs);
  }

  /** Flatten(data) */
  public static Symbol Flatten(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Flatten", name, attrs, new String[] {"data"}, inputs);
  }

  /** FullyConnected(data, weight, bias) */
  public static Symbol FullyConnected(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("FullyConnected", name, attrs, new String[] {"data", "weight", "bias"}, inputs);
  }

  /** GridGenerator(data) */
  public static Symbol GridGenerator(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("GridGenerator", name, attrs, new String[] {"data"}, inputs);
  }

  /** IdentityAttachKLSparseReg(data) */
  public static Symbol IdentityAttachKLSparseReg(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("IdentityAttachKLSparseReg", name, attrs, new String[] {"data"}, inputs);
  }

  /** InstanceNorm(data, gamma, beta) */
  public static Symbol InstanceNorm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("InstanceNorm", name, attrs, new String[] {"data", "gamma", "beta"}, inputs);
  }

  /** L2Normalization(data) */
  public static Symbol L2Normalization(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("L2Normalization", name, attrs, new String[] {"data"}, inputs);
  }

  /** LRN(data) */
  public static Symbol LRN(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("LRN", name, attrs, new String[] {"data"}, inputs);
  }

  /** LayerNorm(data, gamma, beta) */
  public static Symbol LayerNorm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("LayerNorm", name, attrs, new String[] {"data", "gamma", "beta"}, inputs);
  }

  /** LeakyReLU(data) */
  public static Symbol LeakyReLU(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("LeakyReLU", name, attrs, new String[] {"data"}, inputs);
  }

  /** LinearRegressionOutput(data, label) */
  public static Symbol LinearRegressionOutput(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("LinearRegressionOutput", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** LogisticRegressionOutput(data, label) */
  public static Symbol LogisticRegressionOutput(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("LogisticRegressionOutput", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** MAERegressionOutput(data, label) */
  public static Symbol MAERegressionOutput(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("MAERegressionOutput", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** MakeLoss(data) */
  public static Symbol MakeLoss(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("MakeLoss", name, attrs, new String[] {"data"}, inputs);
  }

  /** MultiBoxDetection(cls_prob, loc_pred, anchor) */
  public static Symbol MultiBoxDetection(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("MultiBoxDetection", name, attrs, new String[] {"cls_prob", "loc_pred", "anchor"}, inputs);
  }

  /** MultiBoxPrior(data) */
  public static Symbol MultiBoxPrior(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("MultiBoxPrior", name, attrs, new String[] {"data"}, inputs);
  }

  /** MultiBoxTarget(anchor, label, cls_pred) */
  public static Symbol MultiBoxTarget(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("MultiBoxTarget", name, attrs, new String[] {"anchor", "label", "cls_pred"}, inputs);
  }

  /** MultiProposal(cls_prob, bbox_pred, im_info) */
  public static Symbol MultiProposal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("MultiProposal", name, attrs, new String[] {"cls_prob", "bbox_pred", "im_info"}, inputs);
  }

  /** PSROIPooling(data, rois) */
  public static Symbol PSROIPooling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("PSROIPooling", name, attrs, new String[] {"data", "rois"}, inputs);
  }

  /** Pad(data) */
  public static Symbol Pad(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Pad", name, attrs, new String[] {"data"}, inputs);
  }

  /** Pooling(data) */
  public static Symbol Pooling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Pooling", name, attrs, new String[] {"data"}, inputs);
  }

  /** Pooling_v1(data) */
  public static Symbol Pooling_v1(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Pooling_v1", name, attrs, new String[] {"data"}, inputs);
  }

  /** Proposal(cls_prob, bbox_pred, im_info) */
  public static Symbol Proposal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Proposal", name, attrs, new String[] {"cls_prob", "bbox_pred", "im_info"}, inputs);
  }

  /** RMSNorm(data, gamma) */
  public static Symbol RMSNorm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("RMSNorm", name, attrs, new String[] {"data", "gamma"}, inputs);
  }

  /** RNN(data, parameters, state) */
  public static Symbol RNN(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("RNN", name, attrs, new String[] {"data", "parameters", "state"}, inputs);
  }

  /** ROIPooling(data, rois) */
  public static Symbol ROIPooling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ROIPooling", name, attrs, new String[] {"data", "rois"}, inputs);
  }

  /** Reshape(data) */
  public static Symbol Reshape(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Reshape", name, attrs, new String[] {"data"}, inputs);
  }

  /** SVMOutput(data, label) */
  public static Symbol SVMOutput(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SVMOutput", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** SequenceLast(data) */
  public static Symbol SequenceLast(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SequenceLast", name, attrs, new String[] {"data"}, inputs);
  }

  /** SequenceMask(data) */
  public static Symbol SequenceMask(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SequenceMask", name, attrs, new String[] {"data"}, inputs);
  }

  /** SequenceReverse(data) */
  public static Symbol SequenceReverse(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SequenceReverse", name, attrs, new String[] {"data"}, inputs);
  }

  /** SliceChannel(data) */
  public static Symbol SliceChannel(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SliceChannel", name, attrs, new String[] {"data"}, inputs);
  }

  /** Softmax(data, label) */
  public static Symbol Softmax(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("Softmax", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** SoftmaxActivation(data) */
  public static Symbol SoftmaxActivation(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SoftmaxActivation", name, attrs, new String[] {"data"}, inputs);
  }

  /** SoftmaxOutput(data, label) */
  public static Symbol SoftmaxOutput(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SoftmaxOutput", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** SpatialTransformer(data, loc) */
  public static Symbol SpatialTransformer(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SpatialTransformer", name, attrs, new String[] {"data", "loc"}, inputs);
  }

  /** SwapAxis(data) */
  public static Symbol SwapAxis(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("SwapAxis", name, attrs, new String[] {"data"}, inputs);
  }

  /** UpSampling(variadic) */
  public static Symbol UpSampling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("UpSampling", name, attrs, null, inputs);
  }

  /** _Custom(variadic) */
  public static Symbol _Custom(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_Custom", name, attrs, null, inputs);
  }

  /** _NoGradient() */
  public static Symbol _NoGradient(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_NoGradient", name, attrs, new String[] {}, inputs);
  }

  /** _add(lhs, rhs) */
  public static Symbol _add(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_add", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _arange() */
  public static Symbol _arange(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_arange", name, attrs, new String[] {}, inputs);
  }

  /** _contrib_CTCLoss(data, label) */
  public static Symbol _contrib_CTCLoss(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_CTCLoss", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** _contrib_CausalConv1D(data, weight) */
  public static Symbol _contrib_CausalConv1D(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_CausalConv1D", name, attrs, new String[] {"data", "weight"}, inputs);
  }

  /** _contrib_DeformableConvolution(data, offset, weight) */
  public static Symbol _contrib_DeformableConvolution(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_DeformableConvolution", name, attrs, new String[] {"data", "offset", "weight"}, inputs);
  }

  /** _contrib_DeformablePSROIPooling(data, rois, trans) */
  public static Symbol _contrib_DeformablePSROIPooling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_DeformablePSROIPooling", name, attrs, new String[] {"data", "rois", "trans"}, inputs);
  }

  /** _contrib_FlashAttention(query, key, value) */
  public static Symbol _contrib_FlashAttention(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_FlashAttention", name, attrs, new String[] {"query", "key", "value"}, inputs);
  }

  /** _contrib_GatedDeltaRule(query, key, value, g, beta) */
  public static Symbol _contrib_GatedDeltaRule(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_GatedDeltaRule", name, attrs, new String[] {"query", "key", "value", "g", "beta"}, inputs);
  }

  /** _contrib_HeadGate(data, gate) */
  public static Symbol _contrib_HeadGate(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_HeadGate", name, attrs, new String[] {"data", "gate"}, inputs);
  }

  /** _contrib_HeadNormRotary(data, gamma) */
  public static Symbol _contrib_HeadNormRotary(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_HeadNormRotary", name, attrs, new String[] {"data", "gamma"}, inputs);
  }

  /** _contrib_MoEExperts(data, topk_weight, topk_index, gate_weight, up_weight, down_weight) */
  public static Symbol _contrib_MoEExperts(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_MoEExperts", name, attrs, new String[] {"data", "topk_weight", "topk_index", "gate_weight", "up_weight", "down_weight"}, inputs);
  }

  /** _contrib_MoERouter(data, weight) */
  public static Symbol _contrib_MoERouter(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_MoERouter", name, attrs, new String[] {"data", "weight"}, inputs);
  }

  /** _contrib_MultiBoxDetection(cls_prob, loc_pred, anchor) */
  public static Symbol _contrib_MultiBoxDetection(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_MultiBoxDetection", name, attrs, new String[] {"cls_prob", "loc_pred", "anchor"}, inputs);
  }

  /** _contrib_MultiBoxPrior(data) */
  public static Symbol _contrib_MultiBoxPrior(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_MultiBoxPrior", name, attrs, new String[] {"data"}, inputs);
  }

  /** _contrib_MultiBoxTarget(anchor, label, cls_pred) */
  public static Symbol _contrib_MultiBoxTarget(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_MultiBoxTarget", name, attrs, new String[] {"anchor", "label", "cls_pred"}, inputs);
  }

  /** _contrib_MultiProposal(cls_prob, bbox_pred, im_info) */
  public static Symbol _contrib_MultiProposal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_MultiProposal", name, attrs, new String[] {"cls_prob", "bbox_pred", "im_info"}, inputs);
  }

  /** _contrib_PSROIPooling(data, rois) */
  public static Symbol _contrib_PSROIPooling(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_PSROIPooling", name, attrs, new String[] {"data", "rois"}, inputs);
  }

  /** _contrib_Proposal(cls_prob, bbox_pred, im_info) */
  public static Symbol _contrib_Proposal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_Proposal", name, attrs, new String[] {"cls_prob", "bbox_pred", "im_info"}, inputs);
  }

  /** _contrib_RotaryEmbedding(data) */
  public static Symbol _contrib_RotaryEmbedding(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_RotaryEmbedding", name, attrs, new String[] {"data"}, inputs);
  }

  /** _contrib_SSDScan(data, dt, A_log, B, C, D) */
  public static Symbol _contrib_SSDScan(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_SSDScan", name, attrs, new String[] {"data", "dt", "A_log", "B", "C", "D"}, inputs);
  }

  /** _contrib_count_sketch(data, h, s) */
  public static Symbol _contrib_count_sketch(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_count_sketch", name, attrs, new String[] {"data", "h", "s"}, inputs);
  }

  /** _contrib_ctc_loss(data, label) */
  public static Symbol _contrib_ctc_loss(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_ctc_loss", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** _contrib_dequantize(data, min_range, max_range) */
  public static Symbol _contrib_dequantize(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_dequantize", name, attrs, new String[] {"data", "min_range", "max_range"}, inputs);
  }

  /** _contrib_fft(data) */
  public static Symbol _contrib_fft(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_fft", name, attrs, new String[] {"data"}, inputs);
  }

  /** _contrib_ifft(data) */
  public static Symbol _contrib_ifft(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_ifft", name, attrs, new String[] {"data"}, inputs);
  }

  /** _contrib_krprod(variadic) */
  public static Symbol _contrib_krprod(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_krprod", name, attrs, null, inputs);
  }

  /** _contrib_quantize(data, min_range, max_range) */
  public static Symbol _contrib_quantize(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_contrib_quantize", name, attrs, new String[] {"data", "min_range", "max_range"}, inputs);
  }

  /** _copy(data) */
  public static Symbol _copy(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_copy", name, attrs, new String[] {"data"}, inputs);
  }

  /** _crop_assign(lhs, rhs) */
  public static Symbol _crop_assign(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_crop_assign", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _crop_assign_scalar(data) */
  public static Symbol _crop_assign_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_crop_assign_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _div(lhs, rhs) */
  public static Symbol _div(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_div", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _div_scalar(data) */
  public static Symbol _div_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_div_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _equal(lhs, rhs) */
  public static Symbol _equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _equal_scalar(data) */
  public static Symbol _equal_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_equal_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _full() */
  public static Symbol _full(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_full", name, attrs, new String[] {}, inputs);
  }

  /** _grad_add(lhs, rhs) */
  public static Symbol _grad_add(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_grad_add", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _greater(lhs, rhs) */
  public static Symbol _greater(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_greater", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _greater_equal(lhs, rhs) */
  public static Symbol _greater_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_greater_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _greater_equal_scalar(data) */
  public static Symbol _greater_equal_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_greater_equal_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _greater_scalar(data) */
  public static Symbol _greater_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_greater_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _hypot(lhs, rhs) */
  public static Symbol _hypot(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_hypot", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _hypot_scalar(data) */
  public static Symbol _hypot_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_hypot_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _identity_with_attr_like_rhs(lhs, rhs) */
  public static Symbol _identity_with_attr_like_rhs(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_identity_with_attr_like_rhs", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _khatri_rao(variadic) */
  public static Symbol _khatri_rao(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_khatri_rao", name, attrs, null, inputs);
  }

  /** _lesser(lhs, rhs) */
  public static Symbol _lesser(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_lesser", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _lesser_equal(lhs, rhs) */
  public static Symbol _lesser_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_lesser_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _lesser_equal_scalar(data) */
  public static Symbol _lesser_equal_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_lesser_equal_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _lesser_scalar(data) */
  public static Symbol _lesser_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_lesser_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _linalg_gelqf(A) */
  public static Symbol _linalg_gelqf(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_gelqf", name, attrs, new String[] {"A"}, inputs);
  }

  /** _linalg_gemm(A, B, C) */
  public static Symbol _linalg_gemm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_gemm", name, attrs, new String[] {"A", "B", "C"}, inputs);
  }

  /** _linalg_gemm2(A, B) */
  public static Symbol _linalg_gemm2(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_gemm2", name, attrs, new String[] {"A", "B"}, inputs);
  }

  /** _linalg_potrf(A) */
  public static Symbol _linalg_potrf(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_potrf", name, attrs, new String[] {"A"}, inputs);
  }

  /** _linalg_potri(A) */
  public static Symbol _linalg_potri(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_potri", name, attrs, new String[] {"A"}, inputs);
  }

  /** _linalg_sumlogdiag(A) */
  public static Symbol _linalg_sumlogdiag(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_sumlogdiag", name, attrs, new String[] {"A"}, inputs);
  }

  /** _linalg_syrk(A) */
  public static Symbol _linalg_syrk(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_syrk", name, attrs, new String[] {"A"}, inputs);
  }

  /** _linalg_trmm(A, B) */
  public static Symbol _linalg_trmm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_trmm", name, attrs, new String[] {"A", "B"}, inputs);
  }

  /** _linalg_trsm(A, B) */
  public static Symbol _linalg_trsm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_linalg_trsm", name, attrs, new String[] {"A", "B"}, inputs);
  }

  /** _maximum(lhs, rhs) */
  public static Symbol _maximum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_maximum", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _maximum_scalar(data) */
  public static Symbol _maximum_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_maximum_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _minimum(lhs, rhs) */
  public static Symbol _minimum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_minimum", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _minimum_scalar(data) */
  public static Symbol _minimum_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_minimum_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _minus(lhs, rhs) */
  public static Symbol _minus(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_minus", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _minus_scalar(data) */
  public static Symbol _minus_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_minus_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _mod(lhs, rhs) */
  public static Symbol _mod(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_mod", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _mod_scalar(data) */
  public static Symbol _mod_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_mod_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _mul(lhs, rhs) */
  public static Symbol _mul(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_mul", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _mul_scalar(data) */
  public static Symbol _mul_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_mul_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _not_equal(lhs, rhs) */
  public static Symbol _not_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_not_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _not_equal_scalar(data) */
  public static Symbol _not_equal_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_not_equal_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _ones() */
  public static Symbol _ones(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_ones", name, attrs, new String[] {}, inputs);
  }

  /** _plus(lhs, rhs) */
  public static Symbol _plus(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_plus", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _plus_scalar(data) */
  public static Symbol _plus_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_plus_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _power(lhs, rhs) */
  public static Symbol _power(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_power", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _power_scalar(data) */
  public static Symbol _power_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_power_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _random_exponential() */
  public static Symbol _random_exponential(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_exponential", name, attrs, new String[] {}, inputs);
  }

  /** _random_gamma() */
  public static Symbol _random_gamma(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_gamma", name, attrs, new String[] {}, inputs);
  }

  /** _random_generalized_negative_binomial() */
  public static Symbol _random_generalized_negative_binomial(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_generalized_negative_binomial", name, attrs, new String[] {}, inputs);
  }

  /** _random_negative_binomial() */
  public static Symbol _random_negative_binomial(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_negative_binomial", name, attrs, new String[] {}, inputs);
  }

  /** _random_normal() */
  public static Symbol _random_normal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_normal", name, attrs, new String[] {}, inputs);
  }

  /** _random_poisson() */
  public static Symbol _random_poisson(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_poisson", name, attrs, new String[] {}, inputs);
  }

  /** _random_uniform() */
  public static Symbol _random_uniform(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_random_uniform", name, attrs, new String[] {}, inputs);
  }

  /** _rdiv_scalar(data) */
  public static Symbol _rdiv_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_rdiv_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _rminus_scalar(data) */
  public static Symbol _rminus_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_rminus_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _rmod_scalar(data) */
  public static Symbol _rmod_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_rmod_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _rpower_scalar(data) */
  public static Symbol _rpower_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_rpower_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _slice_assign(lhs, rhs) */
  public static Symbol _slice_assign(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_slice_assign", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _slice_assign_scalar(data) */
  public static Symbol _slice_assign_scalar(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_slice_assign_scalar", name, attrs, new String[] {"data"}, inputs);
  }

  /** _square_sum(data) */
  public static Symbol _square_sum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_square_sum", name, attrs, new String[] {"data"}, inputs);
  }

  /** _sub(lhs, rhs) */
  public static Symbol _sub(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_sub", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** _sum(variadic) */
  public static Symbol _sum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_sum", name, attrs, null, inputs);
  }

  /** _zeros() */
  public static Symbol _zeros(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("_zeros", name, attrs, new String[] {}, inputs);
  }

  /** abs(data) */
  public static Symbol abs(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("abs", name, attrs, new String[] {"data"}, inputs);
  }

  /** adam_update(weight, grad, mean, var) */
  public static Symbol adam_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("adam_update", name, attrs, new String[] {"weight", "grad", "mean", "var"}, inputs);
  }

  /** add_n(variadic) */
  public static Symbol add_n(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("add_n", name, attrs, null, inputs);
  }

  /** arccos(data) */
  public static Symbol arccos(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("arccos", name, attrs, new String[] {"data"}, inputs);
  }

  /** arccosh(data) */
  public static Symbol arccosh(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("arccosh", name, attrs, new String[] {"data"}, inputs);
  }

  /** arcsin(data) */
  public static Symbol arcsin(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("arcsin", name, attrs, new String[] {"data"}, inputs);
  }

  /** arcsinh(data) */
  public static Symbol arcsinh(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("arcsinh", name, attrs, new String[] {"data"}, inputs);
  }

  /** arctan(data) */
  public static Symbol arctan(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("arctan", name, attrs, new String[] {"data"}, inputs);
  }

  /** arctanh(data) */
  public static Symbol arctanh(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("arctanh", name, attrs, new String[] {"data"}, inputs);
  }

  /** argmax(data) */
  public static Symbol argmax(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("argmax", name, attrs, new String[] {"data"}, inputs);
  }

  /** argmax_channel(data) */
  public static Symbol argmax_channel(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("argmax_channel", name, attrs, new String[] {"data"}, inputs);
  }

  /** argmin(data) */
  public static Symbol argmin(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("argmin", name, attrs, new String[] {"data"}, inputs);
  }

  /** argsort(data) */
  public static Symbol argsort(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("argsort", name, attrs, new String[] {"data"}, inputs);
  }

  /** batch_dot(lhs, rhs) */
  public static Symbol batch_dot(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("batch_dot", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** batch_take(a, indices) */
  public static Symbol batch_take(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("batch_take", name, attrs, new String[] {"a", "indices"}, inputs);
  }

  /** broadcast_add(lhs, rhs) */
  public static Symbol broadcast_add(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_add", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_axes(data) */
  public static Symbol broadcast_axes(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_axes", name, attrs, new String[] {"data"}, inputs);
  }

  /** broadcast_axis(data) */
  public static Symbol broadcast_axis(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_axis", name, attrs, new String[] {"data"}, inputs);
  }

  /** broadcast_div(lhs, rhs) */
  public static Symbol broadcast_div(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_div", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_equal(lhs, rhs) */
  public static Symbol broadcast_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_greater(lhs, rhs) */
  public static Symbol broadcast_greater(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_greater", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_greater_equal(lhs, rhs) */
  public static Symbol broadcast_greater_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_greater_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_hypot(lhs, rhs) */
  public static Symbol broadcast_hypot(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_hypot", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_lesser(lhs, rhs) */
  public static Symbol broadcast_lesser(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_lesser", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_lesser_equal(lhs, rhs) */
  public static Symbol broadcast_lesser_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_lesser_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_maximum(lhs, rhs) */
  public static Symbol broadcast_maximum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_maximum", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_minimum(lhs, rhs) */
  public static Symbol broadcast_minimum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_minimum", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_minus(lhs, rhs) */
  public static Symbol broadcast_minus(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_minus", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_mod(lhs, rhs) */
  public static Symbol broadcast_mod(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_mod", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_mul(lhs, rhs) */
  public static Symbol broadcast_mul(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_mul", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_not_equal(lhs, rhs) */
  public static Symbol broadcast_not_equal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_not_equal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_plus(lhs, rhs) */
  public static Symbol broadcast_plus(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_plus", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_power(lhs, rhs) */
  public static Symbol broadcast_power(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_power", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_sub(lhs, rhs) */
  public static Symbol broadcast_sub(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_sub", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** broadcast_to(data) */
  public static Symbol broadcast_to(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("broadcast_to", name, attrs, new String[] {"data"}, inputs);
  }

  /** cast(data) */
  public static Symbol cast(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("cast", name, attrs, new String[] {"data"}, inputs);
  }

  /** cast_storage(data) */
  public static Symbol cast_storage(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("cast_storage", name, attrs, new String[] {"data"}, inputs);
  }

  /** causal_conv1d(data, weight) */
  public static Symbol causal_conv1d(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("causal_conv1d", name, attrs, new String[] {"data", "weight"}, inputs);
  }

  /** cbrt(data) */
  public static Symbol cbrt(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("cbrt", name, attrs, new String[] {"data"}, inputs);
  }

  /** ceil(data) */
  public static Symbol ceil(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ceil", name, attrs, new String[] {"data"}, inputs);
  }

  /** clip(data) */
  public static Symbol clip(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("clip", name, attrs, new String[] {"data"}, inputs);
  }

  /** concat(variadic) */
  public static Symbol concat(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("concat", name, attrs, null, inputs);
  }

  /** cos(data) */
  public static Symbol cos(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("cos", name, attrs, new String[] {"data"}, inputs);
  }

  /** cosh(data) */
  public static Symbol cosh(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("cosh", name, attrs, new String[] {"data"}, inputs);
  }

  /** crop(data) */
  public static Symbol crop(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("crop", name, attrs, new String[] {"data"}, inputs);
  }

  /** ctc_loss(data, label) */
  public static Symbol ctc_loss(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ctc_loss", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** degrees(data) */
  public static Symbol degrees(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("degrees", name, attrs, new String[] {"data"}, inputs);
  }

  /** dequantize_int8(data) */
  public static Symbol dequantize_int8(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("dequantize_int8", name, attrs, new String[] {"data"}, inputs);
  }

  /** dot(lhs, rhs) */
  public static Symbol dot(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("dot", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** elemwise_add(lhs, rhs) */
  public static Symbol elemwise_add(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("elemwise_add", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** elemwise_div(lhs, rhs) */
  public static Symbol elemwise_div(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("elemwise_div", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** elemwise_mul(lhs, rhs) */
  public static Symbol elemwise_mul(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("elemwise_mul", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** elemwise_sub(lhs, rhs) */
  public static Symbol elemwise_sub(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("elemwise_sub", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** erf(data) */
  public static Symbol erf(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("erf", name, attrs, new String[] {"data"}, inputs);
  }

  /** exp(data) */
  public static Symbol exp(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("exp", name, attrs, new String[] {"data"}, inputs);
  }

  /** expand_dims(data) */
  public static Symbol expand_dims(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("expand_dims", name, attrs, new String[] {"data"}, inputs);
  }

  /** expm1(data) */
  public static Symbol expm1(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("expm1", name, attrs, new String[] {"data"}, inputs);
  }

  /** fix(data) */
  public static Symbol fix(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("fix", name, attrs, new String[] {"data"}, inputs);
  }

  /** flash_attention(query, key, value) */
  public static Symbol flash_attention(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("flash_attention", name, attrs, new String[] {"query", "key", "value"}, inputs);
  }

  /** flatten(data) */
  public static Symbol flatten(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("flatten", name, attrs, new String[] {"data"}, inputs);
  }

  /** flip(data) */
  public static Symbol flip(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("flip", name, attrs, new String[] {"data"}, inputs);
  }

  /** floor(data) */
  public static Symbol floor(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("floor", name, attrs, new String[] {"data"}, inputs);
  }

  /** ftrl_update(weight, grad, z, n) */
  public static Symbol ftrl_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ftrl_update", name, attrs, new String[] {"weight", "grad", "z", "n"}, inputs);
  }

  /** gamma(data) */
  public static Symbol gamma(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("gamma", name, attrs, new String[] {"data"}, inputs);
  }

  /** gammaln(data) */
  public static Symbol gammaln(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("gammaln", name, attrs, new String[] {"data"}, inputs);
  }

  /** gated_delta_rule(query, key, value, g, beta) */
  public static Symbol gated_delta_rule(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("gated_delta_rule", name, attrs, new String[] {"query", "key", "value", "g", "beta"}, inputs);
  }

  /** gather_nd(data, indices) */
  public static Symbol gather_nd(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("gather_nd", name, attrs, new String[] {"data", "indices"}, inputs);
  }

  /** head_gate(data, gate) */
  public static Symbol head_gate(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("head_gate", name, attrs, new String[] {"data", "gate"}, inputs);
  }

  /** head_norm_rotary(data, gamma) */
  public static Symbol head_norm_rotary(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("head_norm_rotary", name, attrs, new String[] {"data", "gamma"}, inputs);
  }

  /** identity(data) */
  public static Symbol identity(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("identity", name, attrs, new String[] {"data"}, inputs);
  }

  /** khatri_rao(variadic) */
  public static Symbol khatri_rao(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("khatri_rao", name, attrs, null, inputs);
  }

  /** linalg_gelqf(A) */
  public static Symbol linalg_gelqf(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_gelqf", name, attrs, new String[] {"A"}, inputs);
  }

  /** linalg_gemm(A, B, C) */
  public static Symbol linalg_gemm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_gemm", name, attrs, new String[] {"A", "B", "C"}, inputs);
  }

  /** linalg_gemm2(A, B) */
  public static Symbol linalg_gemm2(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_gemm2", name, attrs, new String[] {"A", "B"}, inputs);
  }

  /** linalg_potrf(A) */
  public static Symbol linalg_potrf(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_potrf", name, attrs, new String[] {"A"}, inputs);
  }

  /** linalg_potri(A) */
  public static Symbol linalg_potri(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_potri", name, attrs, new String[] {"A"}, inputs);
  }

  /** linalg_sumlogdiag(A) */
  public static Symbol linalg_sumlogdiag(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_sumlogdiag", name, attrs, new String[] {"A"}, inputs);
  }

  /** linalg_syrk(A) */
  public static Symbol linalg_syrk(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_syrk", name, attrs, new String[] {"A"}, inputs);
  }

  /** linalg_trmm(A, B) */
  public static Symbol linalg_trmm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_trmm", name, attrs, new String[] {"A", "B"}, inputs);
  }

  /** linalg_trsm(A, B) */
  public static Symbol linalg_trsm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("linalg_trsm", name, attrs, new String[] {"A", "B"}, inputs);
  }

  /** log(data) */
  public static Symbol log(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("log", name, attrs, new String[] {"data"}, inputs);
  }

  /** log10(data) */
  public static Symbol log10(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("log10", name, attrs, new String[] {"data"}, inputs);
  }

  /** log1p(data) */
  public static Symbol log1p(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("log1p", name, attrs, new String[] {"data"}, inputs);
  }

  /** log2(data) */
  public static Symbol log2(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("log2", name, attrs, new String[] {"data"}, inputs);
  }

  /** log_softmax(data) */
  public static Symbol log_softmax(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("log_softmax", name, attrs, new String[] {"data"}, inputs);
  }

  /** make_loss(data) */
  public static Symbol make_loss(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("make_loss", name, attrs, new String[] {"data"}, inputs);
  }

  /** max(data) */
  public static Symbol max(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("max", name, attrs, new String[] {"data"}, inputs);
  }

  /** mean(data) */
  public static Symbol mean(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("mean", name, attrs, new String[] {"data"}, inputs);
  }

  /** min(data) */
  public static Symbol min(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("min", name, attrs, new String[] {"data"}, inputs);
  }

  /** moe_experts(data, topk_weight, topk_index, gate_weight, up_weight, down_weight) */
  public static Symbol moe_experts(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("moe_experts", name, attrs, new String[] {"data", "topk_weight", "topk_index", "gate_weight", "up_weight", "down_weight"}, inputs);
  }

  /** moe_router(data, weight) */
  public static Symbol moe_router(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("moe_router", name, attrs, new String[] {"data", "weight"}, inputs);
  }

  /** mp_sgd_mom_update(weight, grad, mom, weight32) */
  public static Symbol mp_sgd_mom_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("mp_sgd_mom_update", name, attrs, new String[] {"weight", "grad", "mom", "weight32"}, inputs);
  }

  /** mp_sgd_update(weight, grad, weight32) */
  public static Symbol mp_sgd_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("mp_sgd_update", name, attrs, new String[] {"weight", "grad", "weight32"}, inputs);
  }

  /** nanprod(data) */
  public static Symbol nanprod(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("nanprod", name, attrs, new String[] {"data"}, inputs);
  }

  /** nansum(data) */
  public static Symbol nansum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("nansum", name, attrs, new String[] {"data"}, inputs);
  }

  /** negative(data) */
  public static Symbol negative(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("negative", name, attrs, new String[] {"data"}, inputs);
  }

  /** norm(data) */
  public static Symbol norm(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("norm", name, attrs, new String[] {"data"}, inputs);
  }

  /** one_hot(data) */
  public static Symbol one_hot(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("one_hot", name, attrs, new String[] {"data"}, inputs);
  }

  /** ones_like(data) */
  public static Symbol ones_like(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ones_like", name, attrs, new String[] {"data"}, inputs);
  }

  /** pad(data) */
  public static Symbol pad(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("pad", name, attrs, new String[] {"data"}, inputs);
  }

  /** pick(data, index) */
  public static Symbol pick(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("pick", name, attrs, new String[] {"data", "index"}, inputs);
  }

  /** prod(data) */
  public static Symbol prod(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("prod", name, attrs, new String[] {"data"}, inputs);
  }

  /** quantize_int8(data) */
  public static Symbol quantize_int8(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("quantize_int8", name, attrs, new String[] {"data"}, inputs);
  }

  /** radians(data) */
  public static Symbol radians(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("radians", name, attrs, new String[] {"data"}, inputs);
  }

  /** rcbrt(data) */
  public static Symbol rcbrt(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("rcbrt", name, attrs, new String[] {"data"}, inputs);
  }

  /** reciprocal(data) */
  public static Symbol reciprocal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("reciprocal", name, attrs, new String[] {"data"}, inputs);
  }

  /** relu(data) */
  public static Symbol relu(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("relu", name, attrs, new String[] {"data"}, inputs);
  }

  /** repeat(data) */
  public static Symbol repeat(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("repeat", name, attrs, new String[] {"data"}, inputs);
  }

  /** reshape(data) */
  public static Symbol reshape(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("reshape", name, attrs, new String[] {"data"}, inputs);
  }

  /** reshape_like(lhs, rhs) */
  public static Symbol reshape_like(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("reshape_like", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** reverse(data) */
  public static Symbol reverse(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("reverse", name, attrs, new String[] {"data"}, inputs);
  }

  /** rint(data) */
  public static Symbol rint(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("rint", name, attrs, new String[] {"data"}, inputs);
  }

  /** rmsprop_update(weight, grad, n) */
  public static Symbol rmsprop_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("rmsprop_update", name, attrs, new String[] {"weight", "grad", "n"}, inputs);
  }

  /** rmspropalex_update(weight, grad, n, g, delta) */
  public static Symbol rmspropalex_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("rmspropalex_update", name, attrs, new String[] {"weight", "grad", "n", "g", "delta"}, inputs);
  }

  /** rotary_embedding(data) */
  public static Symbol rotary_embedding(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("rotary_embedding", name, attrs, new String[] {"data"}, inputs);
  }

  /** round(data) */
  public static Symbol round(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("round", name, attrs, new String[] {"data"}, inputs);
  }

  /** rsqrt(data) */
  public static Symbol rsqrt(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("rsqrt", name, attrs, new String[] {"data"}, inputs);
  }

  /** sample_exponential(data) */
  public static Symbol sample_exponential(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_exponential", name, attrs, new String[] {"data"}, inputs);
  }

  /** sample_gamma(lhs, rhs) */
  public static Symbol sample_gamma(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_gamma", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** sample_generalized_negative_binomial(lhs, rhs) */
  public static Symbol sample_generalized_negative_binomial(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_generalized_negative_binomial", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** sample_multinomial(data) */
  public static Symbol sample_multinomial(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_multinomial", name, attrs, new String[] {"data"}, inputs);
  }

  /** sample_negative_binomial(lhs, rhs) */
  public static Symbol sample_negative_binomial(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_negative_binomial", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** sample_normal(lhs, rhs) */
  public static Symbol sample_normal(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_normal", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** sample_poisson(data) */
  public static Symbol sample_poisson(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_poisson", name, attrs, new String[] {"data"}, inputs);
  }

  /** sample_uniform(lhs, rhs) */
  public static Symbol sample_uniform(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sample_uniform", name, attrs, new String[] {"lhs", "rhs"}, inputs);
  }

  /** scatter_nd(data, indices) */
  public static Symbol scatter_nd(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("scatter_nd", name, attrs, new String[] {"data", "indices"}, inputs);
  }

  /** sgd_mom_update(weight, grad, mom) */
  public static Symbol sgd_mom_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sgd_mom_update", name, attrs, new String[] {"weight", "grad", "mom"}, inputs);
  }

  /** sgd_update(weight, grad) */
  public static Symbol sgd_update(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sgd_update", name, attrs, new String[] {"weight", "grad"}, inputs);
  }

  /** sigmoid(data) */
  public static Symbol sigmoid(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sigmoid", name, attrs, new String[] {"data"}, inputs);
  }

  /** sign(data) */
  public static Symbol sign(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sign", name, attrs, new String[] {"data"}, inputs);
  }

  /** sin(data) */
  public static Symbol sin(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sin", name, attrs, new String[] {"data"}, inputs);
  }

  /** sinh(data) */
  public static Symbol sinh(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sinh", name, attrs, new String[] {"data"}, inputs);
  }

  /** slice(data) */
  public static Symbol slice(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("slice", name, attrs, new String[] {"data"}, inputs);
  }

  /** slice_axis(data) */
  public static Symbol slice_axis(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("slice_axis", name, attrs, new String[] {"data"}, inputs);
  }

  /** smooth_l1(data) */
  public static Symbol smooth_l1(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("smooth_l1", name, attrs, new String[] {"data"}, inputs);
  }

  /** softmax(data) */
  public static Symbol softmax(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("softmax", name, attrs, new String[] {"data"}, inputs);
  }

  /** softmax_cross_entropy(data, label) */
  public static Symbol softmax_cross_entropy(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("softmax_cross_entropy", name, attrs, new String[] {"data", "label"}, inputs);
  }

  /** softsign(data) */
  public static Symbol softsign(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("softsign", name, attrs, new String[] {"data"}, inputs);
  }

  /** sort(data) */
  public static Symbol sort(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sort", name, attrs, new String[] {"data"}, inputs);
  }

  /** space_to_depth(data) */
  public static Symbol space_to_depth(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("space_to_depth", name, attrs, new String[] {"data"}, inputs);
  }

  /** split(data) */
  public static Symbol split(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("split", name, attrs, new String[] {"data"}, inputs);
  }

  /** sqrt(data) */
  public static Symbol sqrt(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sqrt", name, attrs, new String[] {"data"}, inputs);
  }

  /** square(data) */
  public static Symbol square(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("square", name, attrs, new String[] {"data"}, inputs);
  }

  /** ssd_scan(data, dt, A_log, B, C, D) */
  public static Symbol ssd_scan(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("ssd_scan", name, attrs, new String[] {"data", "dt", "A_log", "B", "C", "D"}, inputs);
  }

  /** stack(variadic) */
  public static Symbol stack(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("stack", name, attrs, null, inputs);
  }

  /** stop_gradient(data) */
  public static Symbol stop_gradient(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("stop_gradient", name, attrs, new String[] {"data"}, inputs);
  }

  /** sum(data) */
  public static Symbol sum(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sum", name, attrs, new String[] {"data"}, inputs);
  }

  /** sum_axis(data) */
  public static Symbol sum_axis(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("sum_axis", name, attrs, new String[] {"data"}, inputs);
  }

  /** swapaxes(data) */
  public static Symbol swapaxes(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("swapaxes", name, attrs, new String[] {"data"}, inputs);
  }

  /** take(a, indices) */
  public static Symbol take(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("take", name, attrs, new String[] {"a", "indices"}, inputs);
  }

  /** tan(data) */
  public static Symbol tan(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("tan", name, attrs, new String[] {"data"}, inputs);
  }

  /** tanh(data) */
  public static Symbol tanh(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("tanh", name, attrs, new String[] {"data"}, inputs);
  }

  /** tile(data) */
  public static Symbol tile(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("tile", name, attrs, new String[] {"data"}, inputs);
  }

  /** topk(data) */
  public static Symbol topk(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("topk", name, attrs, new String[] {"data"}, inputs);
  }

  /** transpose(data) */
  public static Symbol transpose(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("transpose", name, attrs, new String[] {"data"}, inputs);
  }

  /** trunc(data) */
  public static Symbol trunc(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("trunc", name, attrs, new String[] {"data"}, inputs);
  }

  /** where(condition, x, y) */
  public static Symbol where(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("where", name, attrs, new String[] {"condition", "x", "y"}, inputs);
  }

  /** zeros_like(data) */
  public static Symbol zeros_like(String name,
      java.util.Map<String, String> attrs, Symbol... inputs) {
    return Symbol.create("zeros_like", name, attrs, new String[] {"data"}, inputs);
  }

}
