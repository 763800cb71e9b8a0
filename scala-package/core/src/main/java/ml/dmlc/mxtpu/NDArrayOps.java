package ml.dmlc.mxtpu;

// GENERATED by scala-package/gen_jvm_ops.py - do not edit.
// One static method per registered op (307 ops). Regenerate after
// registry changes. Parity: the reference scala-package's generated op
// surface (scala-package/macros SymbolMacro/NDArrayMacro over
// MXListAllOpNames).

public final class NDArrayOps {
  private NDArrayOps() {}

  static NDArray[] invoke(
      String op, java.util.Map<String, String> attrs, NDArray[] inputs) {
    String[] keys = null;
    String[] vals = null;
    if (attrs != null && !attrs.isEmpty()) {
      keys = new String[attrs.size()];
      vals = new String[attrs.size()];
      int i = 0;
      for (java.util.Map.Entry<String, String> e : attrs.entrySet()) {
        keys[i] = e.getKey();
        vals[i] = e.getValue();
        ++i;
      }
    }
    return NDArray.invoke(op, inputs, keys, vals);
  }

  /** Activation(data) */
  public static NDArray[] Activation(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Activation", attrs, inputs);
  }

  /** BatchNorm(data, gamma, beta, moving_mean, moving_var) */
  public static NDArray[] BatchNorm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("BatchNorm", attrs, inputs);
  }

  /** BatchNorm_v1(data, gamma, beta, moving_mean, moving_var) */
  public static NDArray[] BatchNorm_v1(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("BatchNorm_v1", attrs, inputs);
  }

  /** BilinearSampler(data, grid) */
  public static NDArray[] BilinearSampler(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("BilinearSampler", attrs, inputs);
  }

  /** BlockGrad(data) */
  public static NDArray[] BlockGrad(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("BlockGrad", attrs, inputs);
  }

  /** CTCLoss(data, label) */
  public static NDArray[] CTCLoss(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("CTCLoss", attrs, inputs);
  }

  /** Cast(data) */
  public static NDArray[] Cast(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Cast", attrs, inputs);
  }

  /** Concat(variadic) */
  public static NDArray[] Concat(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Concat", attrs, inputs);
  }

  /** Convolution(data, weight, bias) */
  public static NDArray[] Convolution(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Convolution", attrs, inputs);
  }

  /** Convolution_v1(data, weight, bias) */
  public static NDArray[] Convolution_v1(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Convolution_v1", attrs, inputs);
  }

  /** Correlation(data1, data2) */
  public static NDArray[] Correlation(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Correlation", attrs, inputs);
  }

  /** Crop(variadic) */
  public static NDArray[] Crop(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Crop", attrs, inputs);
  }

  /** Custom(variadic) */
  public static NDArray[] Custom(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Custom", attrs, inputs);
  }

  /** Deconvolution(data, weight) */
  public static NDArray[] Deconvolution(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Deconvolution", attrs, inputs);
  }

  /** DeformableConvolution(data, offset, weight) */
  public static NDArray[] DeformableConvolution(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("DeformableConvolution", attrs, inputs);
  }

  /** DeformablePSROIPooling(data, rois, trans) */
  public static NDArray[] DeformablePSROIPooling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("DeformablePSROIPooling", attrs, inputs);
  }

  /** Dropout(data) */
  public static NDArray[] Dropout(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Dropout", attrs, inputs);
  }

  /** ElementWiseSum(variadic) */
  public static NDArray[] ElementWiseSum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ElementWiseSum", attrs, inputs);
  }

  /** Embedding(data, weight) */
  public static NDArray[] Embedding(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Embedding", attrs, inputs);
  }

  /** Flatten(data) */
  public static NDArray[] Flatten(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Flatten", attrs, inputs);
  }

  /** FullyConnected(data, weight, bias) */
  public static NDArray[] FullyConnected(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("FullyConnected", attrs, inputs);
  }

  /** GridGenerator(data) */
  public static NDArray[] GridGenerator(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("GridGenerator", attrs, inputs);
  }

  /** IdentityAttachKLSparseReg(data) */
  public static NDArray[] IdentityAttachKLSparseReg(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("IdentityAttachKLSparseReg", attrs, inputs);
  }

  /** InstanceNorm(data, gamma, beta) */
  public static NDArray[] InstanceNorm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("InstanceNorm", attrs, inputs);
  }

  /** L2Normalization(data) */
  public static NDArray[] L2Normalization(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("L2Normalization", attrs, inputs);
  }

  /** LRN(data) */
  public static NDArray[] LRN(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("LRN", attrs, inputs);
  }

  /** LayerNorm(data, gamma, beta) */
  public static NDArray[] LayerNorm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("LayerNorm", attrs, inputs);
  }

  /** LeakyReLU(data) */
  public static NDArray[] LeakyReLU(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("LeakyReLU", attrs, inputs);
  }

  /** LinearRegressionOutput(data, label) */
  public static NDArray[] LinearRegressionOutput(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("LinearRegressionOutput", attrs, inputs);
  }

  /** LogisticRegressionOutput(data, label) */
  public static NDArray[] LogisticRegressionOutput(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("LogisticRegressionOutput", attrs, inputs);
  }

  /** MAERegressionOutput(data, label) */
  public static NDArray[] MAERegressionOutput(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("MAERegressionOutput", attrs, inputs);
  }

  /** MakeLoss(data) */
  public static NDArray[] MakeLoss(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("MakeLoss", attrs, inputs);
  }

  /** MultiBoxDetection(cls_prob, loc_pred, anchor) */
  public static NDArray[] MultiBoxDetection(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("MultiBoxDetection", attrs, inputs);
  }

  /** MultiBoxPrior(data) */
  public static NDArray[] MultiBoxPrior(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("MultiBoxPrior", attrs, inputs);
  }

  /** MultiBoxTarget(anchor, label, cls_pred) */
  public static NDArray[] MultiBoxTarget(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("MultiBoxTarget", attrs, inputs);
  }

  /** MultiProposal(cls_prob, bbox_pred, im_info) */
  public static NDArray[] MultiProposal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("MultiProposal", attrs, inputs);
  }

  /** PSROIPooling(data, rois) */
  public static NDArray[] PSROIPooling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("PSROIPooling", attrs, inputs);
  }

  /** Pad(data) */
  public static NDArray[] Pad(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Pad", attrs, inputs);
  }

  /** Pooling(data) */
  public static NDArray[] Pooling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Pooling", attrs, inputs);
  }

  /** Pooling_v1(data) */
  public static NDArray[] Pooling_v1(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Pooling_v1", attrs, inputs);
  }

  /** Proposal(cls_prob, bbox_pred, im_info) */
  public static NDArray[] Proposal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Proposal", attrs, inputs);
  }

  /** RMSNorm(data, gamma) */
  public static NDArray[] RMSNorm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("RMSNorm", attrs, inputs);
  }

  /** RNN(data, parameters, state) */
  public static NDArray[] RNN(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("RNN", attrs, inputs);
  }

  /** ROIPooling(data, rois) */
  public static NDArray[] ROIPooling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ROIPooling", attrs, inputs);
  }

  /** Reshape(data) */
  public static NDArray[] Reshape(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Reshape", attrs, inputs);
  }

  /** SVMOutput(data, label) */
  public static NDArray[] SVMOutput(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SVMOutput", attrs, inputs);
  }

  /** SequenceLast(data) */
  public static NDArray[] SequenceLast(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SequenceLast", attrs, inputs);
  }

  /** SequenceMask(data) */
  public static NDArray[] SequenceMask(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SequenceMask", attrs, inputs);
  }

  /** SequenceReverse(data) */
  public static NDArray[] SequenceReverse(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SequenceReverse", attrs, inputs);
  }

  /** SliceChannel(data) */
  public static NDArray[] SliceChannel(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SliceChannel", attrs, inputs);
  }

  /** Softmax(data, label) */
  public static NDArray[] Softmax(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("Softmax", attrs, inputs);
  }

  /** SoftmaxActivation(data) */
  public static NDArray[] SoftmaxActivation(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SoftmaxActivation", attrs, inputs);
  }

  /** SoftmaxOutput(data, label) */
  public static NDArray[] SoftmaxOutput(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SoftmaxOutput", attrs, inputs);
  }

  /** SpatialTransformer(data, loc) */
  public static NDArray[] SpatialTransformer(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SpatialTransformer", attrs, inputs);
  }

  /** SwapAxis(data) */
  public static NDArray[] SwapAxis(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("SwapAxis", attrs, inputs);
  }

  /** UpSampling(variadic) */
  public static NDArray[] UpSampling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("UpSampling", attrs, inputs);
  }

  /** _Custom(variadic) */
  public static NDArray[] _Custom(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_Custom", attrs, inputs);
  }

  /** _NoGradient() */
  public static NDArray[] _NoGradient(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_NoGradient", attrs, inputs);
  }

  /** _add(lhs, rhs) */
  public static NDArray[] _add(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_add", attrs, inputs);
  }

  /** _arange() */
  public static NDArray[] _arange(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_arange", attrs, inputs);
  }

  /** _contrib_CTCLoss(data, label) */
  public static NDArray[] _contrib_CTCLoss(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_CTCLoss", attrs, inputs);
  }

  /** _contrib_CausalConv1D(data, weight) */
  public static NDArray[] _contrib_CausalConv1D(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_CausalConv1D", attrs, inputs);
  }

  /** _contrib_DeformableConvolution(data, offset, weight) */
  public static NDArray[] _contrib_DeformableConvolution(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_DeformableConvolution", attrs, inputs);
  }

  /** _contrib_DeformablePSROIPooling(data, rois, trans) */
  public static NDArray[] _contrib_DeformablePSROIPooling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_DeformablePSROIPooling", attrs, inputs);
  }

  /** _contrib_FlashAttention(query, key, value) */
  public static NDArray[] _contrib_FlashAttention(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_FlashAttention", attrs, inputs);
  }

  /** _contrib_GatedDeltaRule(query, key, value, g, beta) */
  public static NDArray[] _contrib_GatedDeltaRule(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_GatedDeltaRule", attrs, inputs);
  }

  /** _contrib_HeadGate(data, gate) */
  public static NDArray[] _contrib_HeadGate(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_HeadGate", attrs, inputs);
  }

  /** _contrib_HeadNormRotary(data, gamma) */
  public static NDArray[] _contrib_HeadNormRotary(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_HeadNormRotary", attrs, inputs);
  }

  /** _contrib_MoEExperts(data, topk_weight, topk_index, gate_weight, up_weight, down_weight) */
  public static NDArray[] _contrib_MoEExperts(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_MoEExperts", attrs, inputs);
  }

  /** _contrib_MoERouter(data, weight) */
  public static NDArray[] _contrib_MoERouter(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_MoERouter", attrs, inputs);
  }

  /** _contrib_MultiBoxDetection(cls_prob, loc_pred, anchor) */
  public static NDArray[] _contrib_MultiBoxDetection(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_MultiBoxDetection", attrs, inputs);
  }

  /** _contrib_MultiBoxPrior(data) */
  public static NDArray[] _contrib_MultiBoxPrior(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_MultiBoxPrior", attrs, inputs);
  }

  /** _contrib_MultiBoxTarget(anchor, label, cls_pred) */
  public static NDArray[] _contrib_MultiBoxTarget(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_MultiBoxTarget", attrs, inputs);
  }

  /** _contrib_MultiProposal(cls_prob, bbox_pred, im_info) */
  public static NDArray[] _contrib_MultiProposal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_MultiProposal", attrs, inputs);
  }

  /** _contrib_PSROIPooling(data, rois) */
  public static NDArray[] _contrib_PSROIPooling(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_PSROIPooling", attrs, inputs);
  }

  /** _contrib_Proposal(cls_prob, bbox_pred, im_info) */
  public static NDArray[] _contrib_Proposal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_Proposal", attrs, inputs);
  }

  /** _contrib_RotaryEmbedding(data) */
  public static NDArray[] _contrib_RotaryEmbedding(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_RotaryEmbedding", attrs, inputs);
  }

  /** _contrib_SSDScan(data, dt, A_log, B, C, D) */
  public static NDArray[] _contrib_SSDScan(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_SSDScan", attrs, inputs);
  }

  /** _contrib_count_sketch(data, h, s) */
  public static NDArray[] _contrib_count_sketch(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_count_sketch", attrs, inputs);
  }

  /** _contrib_ctc_loss(data, label) */
  public static NDArray[] _contrib_ctc_loss(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_ctc_loss", attrs, inputs);
  }

  /** _contrib_dequantize(data, min_range, max_range) */
  public static NDArray[] _contrib_dequantize(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_dequantize", attrs, inputs);
  }

  /** _contrib_fft(data) */
  public static NDArray[] _contrib_fft(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_fft", attrs, inputs);
  }

  /** _contrib_ifft(data) */
  public static NDArray[] _contrib_ifft(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_ifft", attrs, inputs);
  }

  /** _contrib_krprod(variadic) */
  public static NDArray[] _contrib_krprod(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_krprod", attrs, inputs);
  }

  /** _contrib_quantize(data, min_range, max_range) */
  public static NDArray[] _contrib_quantize(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_contrib_quantize", attrs, inputs);
  }

  /** _copy(data) */
  public static NDArray[] _copy(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_copy", attrs, inputs);
  }

  /** _crop_assign(lhs, rhs) */
  public static NDArray[] _crop_assign(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_crop_assign", attrs, inputs);
  }

  /** _crop_assign_scalar(data) */
  public static NDArray[] _crop_assign_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_crop_assign_scalar", attrs, inputs);
  }

  /** _div(lhs, rhs) */
  public static NDArray[] _div(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_div", attrs, inputs);
  }

  /** _div_scalar(data) */
  public static NDArray[] _div_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_div_scalar", attrs, inputs);
  }

  /** _equal(lhs, rhs) */
  public static NDArray[] _equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_equal", attrs, inputs);
  }

  /** _equal_scalar(data) */
  public static NDArray[] _equal_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_equal_scalar", attrs, inputs);
  }

  /** _full() */
  public static NDArray[] _full(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_full", attrs, inputs);
  }

  /** _grad_add(lhs, rhs) */
  public static NDArray[] _grad_add(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_grad_add", attrs, inputs);
  }

  /** _greater(lhs, rhs) */
  public static NDArray[] _greater(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_greater", attrs, inputs);
  }

  /** _greater_equal(lhs, rhs) */
  public static NDArray[] _greater_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_greater_equal", attrs, inputs);
  }

  /** _greater_equal_scalar(data) */
  public static NDArray[] _greater_equal_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_greater_equal_scalar", attrs, inputs);
  }

  /** _greater_scalar(data) */
  public static NDArray[] _greater_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_greater_scalar", attrs, inputs);
  }

  /** _hypot(lhs, rhs) */
  public static NDArray[] _hypot(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_hypot", attrs, inputs);
  }

  /** _hypot_scalar(data) */
  public static NDArray[] _hypot_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_hypot_scalar", attrs, inputs);
  }

  /** _identity_with_attr_like_rhs(lhs, rhs) */
  public static NDArray[] _identity_with_attr_like_rhs(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_identity_with_attr_like_rhs", attrs, inputs);
  }

  /** _khatri_rao(variadic) */
  public static NDArray[] _khatri_rao(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_khatri_rao", attrs, inputs);
  }

  /** _lesser(lhs, rhs) */
  public static NDArray[] _lesser(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_lesser", attrs, inputs);
  }

  /** _lesser_equal(lhs, rhs) */
  public static NDArray[] _lesser_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_lesser_equal", attrs, inputs);
  }

  /** _lesser_equal_scalar(data) */
  public static NDArray[] _lesser_equal_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_lesser_equal_scalar", attrs, inputs);
  }

  /** _lesser_scalar(data) */
  public static NDArray[] _lesser_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_lesser_scalar", attrs, inputs);
  }

  /** _linalg_gelqf(A) */
  public static NDArray[] _linalg_gelqf(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_gelqf", attrs, inputs);
  }

  /** _linalg_gemm(A, B, C) */
  public static NDArray[] _linalg_gemm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_gemm", attrs, inputs);
  }

  /** _linalg_gemm2(A, B) */
  public static NDArray[] _linalg_gemm2(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_gemm2", attrs, inputs);
  }

  /** _linalg_potrf(A) */
  public static NDArray[] _linalg_potrf(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_potrf", attrs, inputs);
  }

  /** _linalg_potri(A) */
  public static NDArray[] _linalg_potri(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_potri", attrs, inputs);
  }

  /** _linalg_sumlogdiag(A) */
  public static NDArray[] _linalg_sumlogdiag(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_sumlogdiag", attrs, inputs);
  }

  /** _linalg_syrk(A) */
  public static NDArray[] _linalg_syrk(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_syrk", attrs, inputs);
  }

  /** _linalg_trmm(A, B) */
  public static NDArray[] _linalg_trmm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_trmm", attrs, inputs);
  }

  /** _linalg_trsm(A, B) */
  public static NDArray[] _linalg_trsm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_linalg_trsm", attrs, inputs);
  }

  /** _maximum(lhs, rhs) */
  public static NDArray[] _maximum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_maximum", attrs, inputs);
  }

  /** _maximum_scalar(data) */
  public static NDArray[] _maximum_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_maximum_scalar", attrs, inputs);
  }

  /** _minimum(lhs, rhs) */
  public static NDArray[] _minimum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_minimum", attrs, inputs);
  }

  /** _minimum_scalar(data) */
  public static NDArray[] _minimum_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_minimum_scalar", attrs, inputs);
  }

  /** _minus(lhs, rhs) */
  public static NDArray[] _minus(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_minus", attrs, inputs);
  }

  /** _minus_scalar(data) */
  public static NDArray[] _minus_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_minus_scalar", attrs, inputs);
  }

  /** _mod(lhs, rhs) */
  public static NDArray[] _mod(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_mod", attrs, inputs);
  }

  /** _mod_scalar(data) */
  public static NDArray[] _mod_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_mod_scalar", attrs, inputs);
  }

  /** _mul(lhs, rhs) */
  public static NDArray[] _mul(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_mul", attrs, inputs);
  }

  /** _mul_scalar(data) */
  public static NDArray[] _mul_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_mul_scalar", attrs, inputs);
  }

  /** _not_equal(lhs, rhs) */
  public static NDArray[] _not_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_not_equal", attrs, inputs);
  }

  /** _not_equal_scalar(data) */
  public static NDArray[] _not_equal_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_not_equal_scalar", attrs, inputs);
  }

  /** _ones() */
  public static NDArray[] _ones(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_ones", attrs, inputs);
  }

  /** _plus(lhs, rhs) */
  public static NDArray[] _plus(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_plus", attrs, inputs);
  }

  /** _plus_scalar(data) */
  public static NDArray[] _plus_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_plus_scalar", attrs, inputs);
  }

  /** _power(lhs, rhs) */
  public static NDArray[] _power(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_power", attrs, inputs);
  }

  /** _power_scalar(data) */
  public static NDArray[] _power_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_power_scalar", attrs, inputs);
  }

  /** _random_exponential() */
  public static NDArray[] _random_exponential(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_exponential", attrs, inputs);
  }

  /** _random_gamma() */
  public static NDArray[] _random_gamma(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_gamma", attrs, inputs);
  }

  /** _random_generalized_negative_binomial() */
  public static NDArray[] _random_generalized_negative_binomial(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_generalized_negative_binomial", attrs, inputs);
  }

  /** _random_negative_binomial() */
  public static NDArray[] _random_negative_binomial(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_negative_binomial", attrs, inputs);
  }

  /** _random_normal() */
  public static NDArray[] _random_normal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_normal", attrs, inputs);
  }

  /** _random_poisson() */
  public static NDArray[] _random_poisson(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_poisson", attrs, inputs);
  }

  /** _random_uniform() */
  public static NDArray[] _random_uniform(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_random_uniform", attrs, inputs);
  }

  /** _rdiv_scalar(data) */
  public static NDArray[] _rdiv_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_rdiv_scalar", attrs, inputs);
  }

  /** _rminus_scalar(data) */
  public static NDArray[] _rminus_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_rminus_scalar", attrs, inputs);
  }

  /** _rmod_scalar(data) */
  public static NDArray[] _rmod_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_rmod_scalar", attrs, inputs);
  }

  /** _rpower_scalar(data) */
  public static NDArray[] _rpower_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_rpower_scalar", attrs, inputs);
  }

  /** _slice_assign(lhs, rhs) */
  public static NDArray[] _slice_assign(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_slice_assign", attrs, inputs);
  }

  /** _slice_assign_scalar(data) */
  public static NDArray[] _slice_assign_scalar(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_slice_assign_scalar", attrs, inputs);
  }

  /** _square_sum(data) */
  public static NDArray[] _square_sum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_square_sum", attrs, inputs);
  }

  /** _sub(lhs, rhs) */
  public static NDArray[] _sub(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_sub", attrs, inputs);
  }

  /** _sum(variadic) */
  public static NDArray[] _sum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_sum", attrs, inputs);
  }

  /** _zeros() */
  public static NDArray[] _zeros(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("_zeros", attrs, inputs);
  }

  /** abs(data) */
  public static NDArray[] abs(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("abs", attrs, inputs);
  }

  /** adam_update(weight, grad, mean, var) */
  public static NDArray[] adam_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("adam_update", attrs, inputs);
  }

  /** add_n(variadic) */
  public static NDArray[] add_n(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("add_n", attrs, inputs);
  }

  /** arccos(data) */
  public static NDArray[] arccos(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("arccos", attrs, inputs);
  }

  /** arccosh(data) */
  public static NDArray[] arccosh(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("arccosh", attrs, inputs);
  }

  /** arcsin(data) */
  public static NDArray[] arcsin(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("arcsin", attrs, inputs);
  }

  /** arcsinh(data) */
  public static NDArray[] arcsinh(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("arcsinh", attrs, inputs);
  }

  /** arctan(data) */
  public static NDArray[] arctan(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("arctan", attrs, inputs);
  }

  /** arctanh(data) */
  public static NDArray[] arctanh(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("arctanh", attrs, inputs);
  }

  /** argmax(data) */
  public static NDArray[] argmax(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("argmax", attrs, inputs);
  }

  /** argmax_channel(data) */
  public static NDArray[] argmax_channel(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("argmax_channel", attrs, inputs);
  }

  /** argmin(data) */
  public static NDArray[] argmin(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("argmin", attrs, inputs);
  }

  /** argsort(data) */
  public static NDArray[] argsort(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("argsort", attrs, inputs);
  }

  /** batch_dot(lhs, rhs) */
  public static NDArray[] batch_dot(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("batch_dot", attrs, inputs);
  }

  /** batch_take(a, indices) */
  public static NDArray[] batch_take(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("batch_take", attrs, inputs);
  }

  /** broadcast_add(lhs, rhs) */
  public static NDArray[] broadcast_add(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_add", attrs, inputs);
  }

  /** broadcast_axes(data) */
  public static NDArray[] broadcast_axes(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_axes", attrs, inputs);
  }

  /** broadcast_axis(data) */
  public static NDArray[] broadcast_axis(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_axis", attrs, inputs);
  }

  /** broadcast_div(lhs, rhs) */
  public static NDArray[] broadcast_div(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_div", attrs, inputs);
  }

  /** broadcast_equal(lhs, rhs) */
  public static NDArray[] broadcast_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_equal", attrs, inputs);
  }

  /** broadcast_greater(lhs, rhs) */
  public static NDArray[] broadcast_greater(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_greater", attrs, inputs);
  }

  /** broadcast_greater_equal(lhs, rhs) */
  public static NDArray[] broadcast_greater_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_greater_equal", attrs, inputs);
  }

  /** broadcast_hypot(lhs, rhs) */
  public static NDArray[] broadcast_hypot(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_hypot", attrs, inputs);
  }

  /** broadcast_lesser(lhs, rhs) */
  public static NDArray[] broadcast_lesser(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_lesser", attrs, inputs);
  }

  /** broadcast_lesser_equal(lhs, rhs) */
  public static NDArray[] broadcast_lesser_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_lesser_equal", attrs, inputs);
  }

  /** broadcast_maximum(lhs, rhs) */
  public static NDArray[] broadcast_maximum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_maximum", attrs, inputs);
  }

  /** broadcast_minimum(lhs, rhs) */
  public static NDArray[] broadcast_minimum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_minimum", attrs, inputs);
  }

  /** broadcast_minus(lhs, rhs) */
  public static NDArray[] broadcast_minus(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_minus", attrs, inputs);
  }

  /** broadcast_mod(lhs, rhs) */
  public static NDArray[] broadcast_mod(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_mod", attrs, inputs);
  }

  /** broadcast_mul(lhs, rhs) */
  public static NDArray[] broadcast_mul(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_mul", attrs, inputs);
  }

  /** broadcast_not_equal(lhs, rhs) */
  public static NDArray[] broadcast_not_equal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_not_equal", attrs, inputs);
  }

  /** broadcast_plus(lhs, rhs) */
  public static NDArray[] broadcast_plus(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_plus", attrs, inputs);
  }

  /** broadcast_power(lhs, rhs) */
  public static NDArray[] broadcast_power(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_power", attrs, inputs);
  }

  /** broadcast_sub(lhs, rhs) */
  public static NDArray[] broadcast_sub(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_sub", attrs, inputs);
  }

  /** broadcast_to(data) */
  public static NDArray[] broadcast_to(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("broadcast_to", attrs, inputs);
  }

  /** cast(data) */
  public static NDArray[] cast(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("cast", attrs, inputs);
  }

  /** cast_storage(data) */
  public static NDArray[] cast_storage(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("cast_storage", attrs, inputs);
  }

  /** causal_conv1d(data, weight) */
  public static NDArray[] causal_conv1d(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("causal_conv1d", attrs, inputs);
  }

  /** cbrt(data) */
  public static NDArray[] cbrt(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("cbrt", attrs, inputs);
  }

  /** ceil(data) */
  public static NDArray[] ceil(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ceil", attrs, inputs);
  }

  /** clip(data) */
  public static NDArray[] clip(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("clip", attrs, inputs);
  }

  /** concat(variadic) */
  public static NDArray[] concat(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("concat", attrs, inputs);
  }

  /** cos(data) */
  public static NDArray[] cos(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("cos", attrs, inputs);
  }

  /** cosh(data) */
  public static NDArray[] cosh(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("cosh", attrs, inputs);
  }

  /** crop(data) */
  public static NDArray[] crop(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("crop", attrs, inputs);
  }

  /** ctc_loss(data, label) */
  public static NDArray[] ctc_loss(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ctc_loss", attrs, inputs);
  }

  /** degrees(data) */
  public static NDArray[] degrees(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("degrees", attrs, inputs);
  }

  /** dequantize_int8(data) */
  public static NDArray[] dequantize_int8(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("dequantize_int8", attrs, inputs);
  }

  /** dot(lhs, rhs) */
  public static NDArray[] dot(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("dot", attrs, inputs);
  }

  /** elemwise_add(lhs, rhs) */
  public static NDArray[] elemwise_add(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("elemwise_add", attrs, inputs);
  }

  /** elemwise_div(lhs, rhs) */
  public static NDArray[] elemwise_div(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("elemwise_div", attrs, inputs);
  }

  /** elemwise_mul(lhs, rhs) */
  public static NDArray[] elemwise_mul(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("elemwise_mul", attrs, inputs);
  }

  /** elemwise_sub(lhs, rhs) */
  public static NDArray[] elemwise_sub(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("elemwise_sub", attrs, inputs);
  }

  /** erf(data) */
  public static NDArray[] erf(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("erf", attrs, inputs);
  }

  /** exp(data) */
  public static NDArray[] exp(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("exp", attrs, inputs);
  }

  /** expand_dims(data) */
  public static NDArray[] expand_dims(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("expand_dims", attrs, inputs);
  }

  /** expm1(data) */
  public static NDArray[] expm1(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("expm1", attrs, inputs);
  }

  /** fix(data) */
  public static NDArray[] fix(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("fix", attrs, inputs);
  }

  /** flash_attention(query, key, value) */
  public static NDArray[] flash_attention(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("flash_attention", attrs, inputs);
  }

  /** flatten(data) */
  public static NDArray[] flatten(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("flatten", attrs, inputs);
  }

  /** flip(data) */
  public static NDArray[] flip(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("flip", attrs, inputs);
  }

  /** floor(data) */
  public static NDArray[] floor(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("floor", attrs, inputs);
  }

  /** ftrl_update(weight, grad, z, n) */
  public static NDArray[] ftrl_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ftrl_update", attrs, inputs);
  }

  /** gamma(data) */
  public static NDArray[] gamma(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("gamma", attrs, inputs);
  }

  /** gammaln(data) */
  public static NDArray[] gammaln(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("gammaln", attrs, inputs);
  }

  /** gated_delta_rule(query, key, value, g, beta) */
  public static NDArray[] gated_delta_rule(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("gated_delta_rule", attrs, inputs);
  }

  /** gather_nd(data, indices) */
  public static NDArray[] gather_nd(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("gather_nd", attrs, inputs);
  }

  /** head_gate(data, gate) */
  public static NDArray[] head_gate(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("head_gate", attrs, inputs);
  }

  /** head_norm_rotary(data, gamma) */
  public static NDArray[] head_norm_rotary(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("head_norm_rotary", attrs, inputs);
  }

  /** identity(data) */
  public static NDArray[] identity(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("identity", attrs, inputs);
  }

  /** khatri_rao(variadic) */
  public static NDArray[] khatri_rao(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("khatri_rao", attrs, inputs);
  }

  /** linalg_gelqf(A) */
  public static NDArray[] linalg_gelqf(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_gelqf", attrs, inputs);
  }

  /** linalg_gemm(A, B, C) */
  public static NDArray[] linalg_gemm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_gemm", attrs, inputs);
  }

  /** linalg_gemm2(A, B) */
  public static NDArray[] linalg_gemm2(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_gemm2", attrs, inputs);
  }

  /** linalg_potrf(A) */
  public static NDArray[] linalg_potrf(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_potrf", attrs, inputs);
  }

  /** linalg_potri(A) */
  public static NDArray[] linalg_potri(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_potri", attrs, inputs);
  }

  /** linalg_sumlogdiag(A) */
  public static NDArray[] linalg_sumlogdiag(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_sumlogdiag", attrs, inputs);
  }

  /** linalg_syrk(A) */
  public static NDArray[] linalg_syrk(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_syrk", attrs, inputs);
  }

  /** linalg_trmm(A, B) */
  public static NDArray[] linalg_trmm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_trmm", attrs, inputs);
  }

  /** linalg_trsm(A, B) */
  public static NDArray[] linalg_trsm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("linalg_trsm", attrs, inputs);
  }

  /** log(data) */
  public static NDArray[] log(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("log", attrs, inputs);
  }

  /** log10(data) */
  public static NDArray[] log10(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("log10", attrs, inputs);
  }

  /** log1p(data) */
  public static NDArray[] log1p(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("log1p", attrs, inputs);
  }

  /** log2(data) */
  public static NDArray[] log2(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("log2", attrs, inputs);
  }

  /** log_softmax(data) */
  public static NDArray[] log_softmax(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("log_softmax", attrs, inputs);
  }

  /** make_loss(data) */
  public static NDArray[] make_loss(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("make_loss", attrs, inputs);
  }

  /** max(data) */
  public static NDArray[] max(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("max", attrs, inputs);
  }

  /** mean(data) */
  public static NDArray[] mean(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("mean", attrs, inputs);
  }

  /** min(data) */
  public static NDArray[] min(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("min", attrs, inputs);
  }

  /** moe_experts(data, topk_weight, topk_index, gate_weight, up_weight, down_weight) */
  public static NDArray[] moe_experts(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("moe_experts", attrs, inputs);
  }

  /** moe_router(data, weight) */
  public static NDArray[] moe_router(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("moe_router", attrs, inputs);
  }

  /** mp_sgd_mom_update(weight, grad, mom, weight32) */
  public static NDArray[] mp_sgd_mom_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("mp_sgd_mom_update", attrs, inputs);
  }

  /** mp_sgd_update(weight, grad, weight32) */
  public static NDArray[] mp_sgd_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("mp_sgd_update", attrs, inputs);
  }

  /** nanprod(data) */
  public static NDArray[] nanprod(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("nanprod", attrs, inputs);
  }

  /** nansum(data) */
  public static NDArray[] nansum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("nansum", attrs, inputs);
  }

  /** negative(data) */
  public static NDArray[] negative(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("negative", attrs, inputs);
  }

  /** norm(data) */
  public static NDArray[] norm(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("norm", attrs, inputs);
  }

  /** one_hot(data) */
  public static NDArray[] one_hot(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("one_hot", attrs, inputs);
  }

  /** ones_like(data) */
  public static NDArray[] ones_like(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ones_like", attrs, inputs);
  }

  /** pad(data) */
  public static NDArray[] pad(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("pad", attrs, inputs);
  }

  /** pick(data, index) */
  public static NDArray[] pick(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("pick", attrs, inputs);
  }

  /** prod(data) */
  public static NDArray[] prod(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("prod", attrs, inputs);
  }

  /** quantize_int8(data) */
  public static NDArray[] quantize_int8(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("quantize_int8", attrs, inputs);
  }

  /** radians(data) */
  public static NDArray[] radians(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("radians", attrs, inputs);
  }

  /** rcbrt(data) */
  public static NDArray[] rcbrt(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("rcbrt", attrs, inputs);
  }

  /** reciprocal(data) */
  public static NDArray[] reciprocal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("reciprocal", attrs, inputs);
  }

  /** relu(data) */
  public static NDArray[] relu(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("relu", attrs, inputs);
  }

  /** repeat(data) */
  public static NDArray[] repeat(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("repeat", attrs, inputs);
  }

  /** reshape(data) */
  public static NDArray[] reshape(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("reshape", attrs, inputs);
  }

  /** reshape_like(lhs, rhs) */
  public static NDArray[] reshape_like(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("reshape_like", attrs, inputs);
  }

  /** reverse(data) */
  public static NDArray[] reverse(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("reverse", attrs, inputs);
  }

  /** rint(data) */
  public static NDArray[] rint(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("rint", attrs, inputs);
  }

  /** rmsprop_update(weight, grad, n) */
  public static NDArray[] rmsprop_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("rmsprop_update", attrs, inputs);
  }

  /** rmspropalex_update(weight, grad, n, g, delta) */
  public static NDArray[] rmspropalex_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("rmspropalex_update", attrs, inputs);
  }

  /** rotary_embedding(data) */
  public static NDArray[] rotary_embedding(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("rotary_embedding", attrs, inputs);
  }

  /** round(data) */
  public static NDArray[] round(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("round", attrs, inputs);
  }

  /** rsqrt(data) */
  public static NDArray[] rsqrt(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("rsqrt", attrs, inputs);
  }

  /** sample_exponential(data) */
  public static NDArray[] sample_exponential(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_exponential", attrs, inputs);
  }

  /** sample_gamma(lhs, rhs) */
  public static NDArray[] sample_gamma(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_gamma", attrs, inputs);
  }

  /** sample_generalized_negative_binomial(lhs, rhs) */
  public static NDArray[] sample_generalized_negative_binomial(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_generalized_negative_binomial", attrs, inputs);
  }

  /** sample_multinomial(data) */
  public static NDArray[] sample_multinomial(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_multinomial", attrs, inputs);
  }

  /** sample_negative_binomial(lhs, rhs) */
  public static NDArray[] sample_negative_binomial(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_negative_binomial", attrs, inputs);
  }

  /** sample_normal(lhs, rhs) */
  public static NDArray[] sample_normal(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_normal", attrs, inputs);
  }

  /** sample_poisson(data) */
  public static NDArray[] sample_poisson(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_poisson", attrs, inputs);
  }

  /** sample_uniform(lhs, rhs) */
  public static NDArray[] sample_uniform(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sample_uniform", attrs, inputs);
  }

  /** scatter_nd(data, indices) */
  public static NDArray[] scatter_nd(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("scatter_nd", attrs, inputs);
  }

  /** sgd_mom_update(weight, grad, mom) */
  public static NDArray[] sgd_mom_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sgd_mom_update", attrs, inputs);
  }

  /** sgd_update(weight, grad) */
  public static NDArray[] sgd_update(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sgd_update", attrs, inputs);
  }

  /** sigmoid(data) */
  public static NDArray[] sigmoid(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sigmoid", attrs, inputs);
  }

  /** sign(data) */
  public static NDArray[] sign(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sign", attrs, inputs);
  }

  /** sin(data) */
  public static NDArray[] sin(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sin", attrs, inputs);
  }

  /** sinh(data) */
  public static NDArray[] sinh(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sinh", attrs, inputs);
  }

  /** slice(data) */
  public static NDArray[] slice(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("slice", attrs, inputs);
  }

  /** slice_axis(data) */
  public static NDArray[] slice_axis(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("slice_axis", attrs, inputs);
  }

  /** smooth_l1(data) */
  public static NDArray[] smooth_l1(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("smooth_l1", attrs, inputs);
  }

  /** softmax(data) */
  public static NDArray[] softmax(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("softmax", attrs, inputs);
  }

  /** softmax_cross_entropy(data, label) */
  public static NDArray[] softmax_cross_entropy(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("softmax_cross_entropy", attrs, inputs);
  }

  /** softsign(data) */
  public static NDArray[] softsign(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("softsign", attrs, inputs);
  }

  /** sort(data) */
  public static NDArray[] sort(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sort", attrs, inputs);
  }

  /** space_to_depth(data) */
  public static NDArray[] space_to_depth(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("space_to_depth", attrs, inputs);
  }

  /** split(data) */
  public static NDArray[] split(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("split", attrs, inputs);
  }

  /** sqrt(data) */
  public static NDArray[] sqrt(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sqrt", attrs, inputs);
  }

  /** square(data) */
  public static NDArray[] square(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("square", attrs, inputs);
  }

  /** ssd_scan(data, dt, A_log, B, C, D) */
  public static NDArray[] ssd_scan(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("ssd_scan", attrs, inputs);
  }

  /** stack(variadic) */
  public static NDArray[] stack(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("stack", attrs, inputs);
  }

  /** stop_gradient(data) */
  public static NDArray[] stop_gradient(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("stop_gradient", attrs, inputs);
  }

  /** sum(data) */
  public static NDArray[] sum(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sum", attrs, inputs);
  }

  /** sum_axis(data) */
  public static NDArray[] sum_axis(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("sum_axis", attrs, inputs);
  }

  /** swapaxes(data) */
  public static NDArray[] swapaxes(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("swapaxes", attrs, inputs);
  }

  /** take(a, indices) */
  public static NDArray[] take(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("take", attrs, inputs);
  }

  /** tan(data) */
  public static NDArray[] tan(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("tan", attrs, inputs);
  }

  /** tanh(data) */
  public static NDArray[] tanh(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("tanh", attrs, inputs);
  }

  /** tile(data) */
  public static NDArray[] tile(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("tile", attrs, inputs);
  }

  /** topk(data) */
  public static NDArray[] topk(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("topk", attrs, inputs);
  }

  /** transpose(data) */
  public static NDArray[] transpose(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("transpose", attrs, inputs);
  }

  /** trunc(data) */
  public static NDArray[] trunc(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("trunc", attrs, inputs);
  }

  /** where(condition, x, y) */
  public static NDArray[] where(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("where", attrs, inputs);
  }

  /** zeros_like(data) */
  public static NDArray[] zeros_like(
      java.util.Map<String, String> attrs, NDArray... inputs) {
    return invoke("zeros_like", attrs, inputs);
  }

}
