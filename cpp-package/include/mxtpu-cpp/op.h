/* GENERATED FILE — do not edit. Regenerate with
 *   python cpp-package/OpWrapperGenerator.py
 * One typed wrapper per registered op (the reference's
 * cpp-package/include/mxnet-cpp/op.h surface, generated from the
 * op registry the same way its OpWrapperGenerator.py does). */
#ifndef MXTPU_CPP_OP_H_
#define MXTPU_CPP_OP_H_

#include <map>
#include <string>
#include <vector>

#include "operator.h"

namespace mxtpu {
namespace cpp {
namespace op {

inline Symbol Activation(const std::string &symbol_name, const Symbol &data, const std::string & act_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Activation");
  op_.SetParam("act_type", act_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Activation(const NDArray &data, const std::string & act_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Activation");
  op_.SetParam("act_type", act_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol BatchNorm(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, const Symbol &beta, const Symbol &moving_mean, const Symbol &moving_var, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BatchNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetInput("moving_mean", moving_mean);
  op_.SetInput("moving_var", moving_var);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> BatchNorm(const NDArray &data, const NDArray &gamma, const NDArray &beta, const NDArray &moving_mean, const NDArray &moving_var, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BatchNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  op_.AddInput(moving_mean);
  op_.AddInput(moving_var);
  return op_.Invoke();
}

inline Symbol BatchNorm_v1(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, const Symbol &beta, const Symbol &moving_mean, const Symbol &moving_var, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BatchNorm_v1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetInput("moving_mean", moving_mean);
  op_.SetInput("moving_var", moving_var);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> BatchNorm_v1(const NDArray &data, const NDArray &gamma, const NDArray &beta, const NDArray &moving_mean, const NDArray &moving_var, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BatchNorm_v1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  op_.AddInput(moving_mean);
  op_.AddInput(moving_var);
  return op_.Invoke();
}

inline Symbol BilinearSampler(const std::string &symbol_name, const Symbol &data, const Symbol &grid, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BilinearSampler");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("grid", grid);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> BilinearSampler(const NDArray &data, const NDArray &grid, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BilinearSampler");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(grid);
  return op_.Invoke();
}

inline Symbol BlockGrad(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BlockGrad");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> BlockGrad(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("BlockGrad");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol CTCLoss(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("CTCLoss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> CTCLoss(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("CTCLoss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol Cast(const std::string &symbol_name, const Symbol &data, const std::string & dtype, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Cast");
  op_.SetParam("dtype", dtype);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Cast(const NDArray &data, const std::string & dtype, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Cast");
  op_.SetParam("dtype", dtype);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol Concat(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Concat");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Concat(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Concat");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol Convolution(const std::string &symbol_name, const Symbol &data, const Symbol &weight, const Symbol &bias, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Convolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetInput("bias", bias);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Convolution(const NDArray &data, const NDArray &weight, const NDArray &bias, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Convolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  op_.AddInput(bias);
  return op_.Invoke();
}

inline Symbol Convolution_v1(const std::string &symbol_name, const Symbol &data, const Symbol &weight, const Symbol &bias, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Convolution_v1");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetInput("bias", bias);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Convolution_v1(const NDArray &data, const NDArray &weight, const NDArray &bias, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Convolution_v1");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  op_.AddInput(bias);
  return op_.Invoke();
}

inline Symbol Correlation(const std::string &symbol_name, const Symbol &data1, const Symbol &data2, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Correlation");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data1", data1);
  op_.SetInput("data2", data2);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Correlation(const NDArray &data1, const NDArray &data2, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Correlation");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data1);
  op_.AddInput(data2);
  return op_.Invoke();
}

inline Symbol Crop(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Crop");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Crop(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Crop");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol Custom(const std::string &symbol_name, const std::vector<Symbol> &data, const std::string & op_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Custom");
  op_.SetParam("op_type", op_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Custom(const std::vector<NDArray> &data, const std::string & op_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Custom");
  op_.SetParam("op_type", op_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol Deconvolution(const std::string &symbol_name, const Symbol &data, const Symbol &weight, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Deconvolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Deconvolution(const NDArray &data, const NDArray &weight, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Deconvolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol DeformableConvolution(const std::string &symbol_name, const Symbol &data, const Symbol &offset, const Symbol &weight, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("DeformableConvolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("offset", offset);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> DeformableConvolution(const NDArray &data, const NDArray &offset, const NDArray &weight, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("DeformableConvolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(offset);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol DeformablePSROIPooling(const std::string &symbol_name, const Symbol &data, const Symbol &rois, const Symbol &trans, double spatial_scale, int output_dim, int group_size, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("DeformablePSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("group_size", group_size);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetInput("trans", trans);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> DeformablePSROIPooling(const NDArray &data, const NDArray &rois, const NDArray &trans, double spatial_scale, int output_dim, int group_size, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("DeformablePSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("group_size", group_size);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(rois);
  op_.AddInput(trans);
  return op_.Invoke();
}

inline Symbol Dropout(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Dropout");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Dropout(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Dropout");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol ElementWiseSum(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ElementWiseSum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ElementWiseSum(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ElementWiseSum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol Embedding(const std::string &symbol_name, const Symbol &data, const Symbol &weight, int input_dim, int output_dim, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Embedding");
  op_.SetParam("input_dim", input_dim);
  op_.SetParam("output_dim", output_dim);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Embedding(const NDArray &data, const NDArray &weight, int input_dim, int output_dim, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Embedding");
  op_.SetParam("input_dim", input_dim);
  op_.SetParam("output_dim", output_dim);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol Flatten(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Flatten");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Flatten(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Flatten");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol FullyConnected(const std::string &symbol_name, const Symbol &data, const Symbol &weight, const Symbol &bias, int num_hidden, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("FullyConnected");
  op_.SetParam("num_hidden", num_hidden);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetInput("bias", bias);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> FullyConnected(const NDArray &data, const NDArray &weight, const NDArray &bias, int num_hidden, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("FullyConnected");
  op_.SetParam("num_hidden", num_hidden);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  op_.AddInput(bias);
  return op_.Invoke();
}

inline Symbol GridGenerator(const std::string &symbol_name, const Symbol &data, const std::string & transform_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("GridGenerator");
  op_.SetParam("transform_type", transform_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> GridGenerator(const NDArray &data, const std::string & transform_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("GridGenerator");
  op_.SetParam("transform_type", transform_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol IdentityAttachKLSparseReg(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("IdentityAttachKLSparseReg");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> IdentityAttachKLSparseReg(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("IdentityAttachKLSparseReg");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol InstanceNorm(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, const Symbol &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("InstanceNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> InstanceNorm(const NDArray &data, const NDArray &gamma, const NDArray &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("InstanceNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  return op_.Invoke();
}

inline Symbol L2Normalization(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("L2Normalization");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> L2Normalization(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("L2Normalization");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol LRN(const std::string &symbol_name, const Symbol &data, int nsize, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LRN");
  op_.SetParam("nsize", nsize);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> LRN(const NDArray &data, int nsize, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LRN");
  op_.SetParam("nsize", nsize);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol LayerNorm(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, const Symbol &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LayerNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> LayerNorm(const NDArray &data, const NDArray &gamma, const NDArray &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LayerNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  op_.AddInput(beta);
  return op_.Invoke();
}

inline Symbol LeakyReLU(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LeakyReLU");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> LeakyReLU(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LeakyReLU");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol LinearRegressionOutput(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LinearRegressionOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> LinearRegressionOutput(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LinearRegressionOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol LogisticRegressionOutput(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LogisticRegressionOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> LogisticRegressionOutput(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("LogisticRegressionOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol MAERegressionOutput(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MAERegressionOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> MAERegressionOutput(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MAERegressionOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol MakeLoss(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MakeLoss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> MakeLoss(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MakeLoss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol MultiBoxDetection(const std::string &symbol_name, const Symbol &cls_prob, const Symbol &loc_pred, const Symbol &anchor, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiBoxDetection");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("loc_pred", loc_pred);
  op_.SetInput("anchor", anchor);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> MultiBoxDetection(const NDArray &cls_prob, const NDArray &loc_pred, const NDArray &anchor, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiBoxDetection");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(cls_prob);
  op_.AddInput(loc_pred);
  op_.AddInput(anchor);
  return op_.Invoke();
}

inline Symbol MultiBoxPrior(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiBoxPrior");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> MultiBoxPrior(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiBoxPrior");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol MultiBoxTarget(const std::string &symbol_name, const Symbol &anchor, const Symbol &label, const Symbol &cls_pred, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiBoxTarget");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("anchor", anchor);
  op_.SetInput("label", label);
  op_.SetInput("cls_pred", cls_pred);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> MultiBoxTarget(const NDArray &anchor, const NDArray &label, const NDArray &cls_pred, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiBoxTarget");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(anchor);
  op_.AddInput(label);
  op_.AddInput(cls_pred);
  return op_.Invoke();
}

inline Symbol MultiProposal(const std::string &symbol_name, const Symbol &cls_prob, const Symbol &bbox_pred, const Symbol &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiProposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> MultiProposal(const NDArray &cls_prob, const NDArray &bbox_pred, const NDArray &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("MultiProposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(cls_prob);
  op_.AddInput(bbox_pred);
  op_.AddInput(im_info);
  return op_.Invoke();
}

inline Symbol PSROIPooling(const std::string &symbol_name, const Symbol &data, const Symbol &rois, double spatial_scale, int output_dim, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("PSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> PSROIPooling(const NDArray &data, const NDArray &rois, double spatial_scale, int output_dim, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("PSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(rois);
  return op_.Invoke();
}

inline Symbol Pad(const std::string &symbol_name, const Symbol &data, const std::string & mode, const Shape & pad_width, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Pad");
  op_.SetParam("mode", mode);
  op_.SetParam("pad_width", pad_width);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Pad(const NDArray &data, const std::string & mode, const Shape & pad_width, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Pad");
  op_.SetParam("mode", mode);
  op_.SetParam("pad_width", pad_width);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol Pooling(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Pooling");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Pooling(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Pooling");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol Pooling_v1(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Pooling_v1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Pooling_v1(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Pooling_v1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol Proposal(const std::string &symbol_name, const Symbol &cls_prob, const Symbol &bbox_pred, const Symbol &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Proposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Proposal(const NDArray &cls_prob, const NDArray &bbox_pred, const NDArray &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Proposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(cls_prob);
  op_.AddInput(bbox_pred);
  op_.AddInput(im_info);
  return op_.Invoke();
}

inline Symbol RMSNorm(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("RMSNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> RMSNorm(const NDArray &data, const NDArray &gamma, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("RMSNorm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  return op_.Invoke();
}

inline Symbol RNN(const std::string &symbol_name, const Symbol &data, const Symbol &parameters, const Symbol &state, int state_size, int num_layers, const std::string & mode, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("RNN");
  op_.SetParam("state_size", state_size);
  op_.SetParam("num_layers", num_layers);
  op_.SetParam("mode", mode);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("parameters", parameters);
  op_.SetInput("state", state);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> RNN(const NDArray &data, const NDArray &parameters, const NDArray &state, int state_size, int num_layers, const std::string & mode, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("RNN");
  op_.SetParam("state_size", state_size);
  op_.SetParam("num_layers", num_layers);
  op_.SetParam("mode", mode);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(parameters);
  op_.AddInput(state);
  return op_.Invoke();
}

inline Symbol ROIPooling(const std::string &symbol_name, const Symbol &data, const Symbol &rois, const Shape & pooled_size, double spatial_scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ROIPooling");
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("spatial_scale", spatial_scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ROIPooling(const NDArray &data, const NDArray &rois, const Shape & pooled_size, double spatial_scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ROIPooling");
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("spatial_scale", spatial_scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(rois);
  return op_.Invoke();
}

inline Symbol Reshape(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Reshape");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Reshape(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Reshape");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol Reshape(const std::string &symbol_name, const Symbol &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Reshape");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Reshape(const NDArray &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Reshape");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol SVMOutput(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SVMOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SVMOutput(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SVMOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol SequenceLast(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SequenceLast");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SequenceLast(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SequenceLast");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol SequenceMask(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SequenceMask");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SequenceMask(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SequenceMask");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol SequenceReverse(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SequenceReverse");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SequenceReverse(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SequenceReverse");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol SliceChannel(const std::string &symbol_name, const Symbol &data, int num_outputs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SliceChannel");
  op_.SetParam("num_outputs", num_outputs);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SliceChannel(const NDArray &data, int num_outputs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SliceChannel");
  op_.SetParam("num_outputs", num_outputs);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol Softmax(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Softmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Softmax(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("Softmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol SoftmaxActivation(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SoftmaxActivation");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SoftmaxActivation(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SoftmaxActivation");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol SoftmaxOutput(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SoftmaxOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SoftmaxOutput(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SoftmaxOutput");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol SpatialTransformer(const std::string &symbol_name, const Symbol &data, const Symbol &loc, const Shape & target_shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SpatialTransformer");
  op_.SetParam("target_shape", target_shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("loc", loc);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SpatialTransformer(const NDArray &data, const NDArray &loc, const Shape & target_shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SpatialTransformer");
  op_.SetParam("target_shape", target_shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(loc);
  return op_.Invoke();
}

inline Symbol SwapAxis(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SwapAxis");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> SwapAxis(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("SwapAxis");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol UpSampling(const std::string &symbol_name, const std::vector<Symbol> &data, int scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("UpSampling");
  op_.SetParam("scale", scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> UpSampling(const std::vector<NDArray> &data, int scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("UpSampling");
  op_.SetParam("scale", scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol Op_Custom(const std::string &symbol_name, const std::vector<Symbol> &data, const std::string & op_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_Custom");
  op_.SetParam("op_type", op_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Op_Custom(const std::vector<NDArray> &data, const std::string & op_type, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_Custom");
  op_.SetParam("op_type", op_type);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol Op_NoGradient(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_NoGradient");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> Op_NoGradient(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_NoGradient");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _add(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _add(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _arange(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_arange");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _arange(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_arange");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _contrib_CTCLoss(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_CTCLoss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_CTCLoss(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_CTCLoss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol _contrib_CausalConv1D(const std::string &symbol_name, const Symbol &data, const Symbol &weight, int kernel, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_CausalConv1D");
  op_.SetParam("kernel", kernel);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_CausalConv1D(const NDArray &data, const NDArray &weight, int kernel, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_CausalConv1D");
  op_.SetParam("kernel", kernel);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol _contrib_DeformableConvolution(const std::string &symbol_name, const Symbol &data, const Symbol &offset, const Symbol &weight, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_DeformableConvolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("offset", offset);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_DeformableConvolution(const NDArray &data, const NDArray &offset, const NDArray &weight, const Shape & kernel, int num_filter, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_DeformableConvolution");
  op_.SetParam("kernel", kernel);
  op_.SetParam("num_filter", num_filter);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(offset);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol _contrib_DeformablePSROIPooling(const std::string &symbol_name, const Symbol &data, const Symbol &rois, const Symbol &trans, double spatial_scale, int output_dim, int group_size, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_DeformablePSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("group_size", group_size);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetInput("trans", trans);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_DeformablePSROIPooling(const NDArray &data, const NDArray &rois, const NDArray &trans, double spatial_scale, int output_dim, int group_size, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_DeformablePSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("group_size", group_size);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(rois);
  op_.AddInput(trans);
  return op_.Invoke();
}

inline Symbol _contrib_FlashAttention(const std::string &symbol_name, const Symbol &query, const Symbol &key, const Symbol &value, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_FlashAttention");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("query", query);
  op_.SetInput("key", key);
  op_.SetInput("value", value);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_FlashAttention(const NDArray &query, const NDArray &key, const NDArray &value, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_FlashAttention");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(query);
  op_.AddInput(key);
  op_.AddInput(value);
  return op_.Invoke();
}

inline Symbol _contrib_GatedDeltaRule(const std::string &symbol_name, const Symbol &query, const Symbol &key, const Symbol &value, const Symbol &g, const Symbol &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_GatedDeltaRule");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("query", query);
  op_.SetInput("key", key);
  op_.SetInput("value", value);
  op_.SetInput("g", g);
  op_.SetInput("beta", beta);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_GatedDeltaRule(const NDArray &query, const NDArray &key, const NDArray &value, const NDArray &g, const NDArray &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_GatedDeltaRule");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(query);
  op_.AddInput(key);
  op_.AddInput(value);
  op_.AddInput(g);
  op_.AddInput(beta);
  return op_.Invoke();
}

inline Symbol _contrib_HeadGate(const std::string &symbol_name, const Symbol &data, const Symbol &gate, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_HeadGate");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gate", gate);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_HeadGate(const NDArray &data, const NDArray &gate, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_HeadGate");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gate);
  return op_.Invoke();
}

inline Symbol _contrib_HeadNormRotary(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, int num_heads, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_HeadNormRotary");
  op_.SetParam("num_heads", num_heads);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_HeadNormRotary(const NDArray &data, const NDArray &gamma, int num_heads, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_HeadNormRotary");
  op_.SetParam("num_heads", num_heads);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  return op_.Invoke();
}

inline Symbol _contrib_MoEExperts(const std::string &symbol_name, const Symbol &data, const Symbol &topk_weight, const Symbol &topk_index, const Symbol &gate_weight, const Symbol &up_weight, const Symbol &down_weight, int num_experts, int experts_held, int hidden, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MoEExperts");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("experts_held", experts_held);
  op_.SetParam("hidden", hidden);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("topk_weight", topk_weight);
  op_.SetInput("topk_index", topk_index);
  op_.SetInput("gate_weight", gate_weight);
  op_.SetInput("up_weight", up_weight);
  op_.SetInput("down_weight", down_weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_MoEExperts(const NDArray &data, const NDArray &topk_weight, const NDArray &topk_index, const NDArray &gate_weight, const NDArray &up_weight, const NDArray &down_weight, int num_experts, int experts_held, int hidden, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MoEExperts");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("experts_held", experts_held);
  op_.SetParam("hidden", hidden);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(topk_weight);
  op_.AddInput(topk_index);
  op_.AddInput(gate_weight);
  op_.AddInput(up_weight);
  op_.AddInput(down_weight);
  return op_.Invoke();
}

inline Symbol _contrib_MoERouter(const std::string &symbol_name, const Symbol &data, const Symbol &weight, int num_experts, int top_k, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MoERouter");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("top_k", top_k);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_MoERouter(const NDArray &data, const NDArray &weight, int num_experts, int top_k, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MoERouter");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("top_k", top_k);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol _contrib_MultiBoxDetection(const std::string &symbol_name, const Symbol &cls_prob, const Symbol &loc_pred, const Symbol &anchor, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiBoxDetection");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("loc_pred", loc_pred);
  op_.SetInput("anchor", anchor);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_MultiBoxDetection(const NDArray &cls_prob, const NDArray &loc_pred, const NDArray &anchor, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiBoxDetection");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(cls_prob);
  op_.AddInput(loc_pred);
  op_.AddInput(anchor);
  return op_.Invoke();
}

inline Symbol _contrib_MultiBoxPrior(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiBoxPrior");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_MultiBoxPrior(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiBoxPrior");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _contrib_MultiBoxTarget(const std::string &symbol_name, const Symbol &anchor, const Symbol &label, const Symbol &cls_pred, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiBoxTarget");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("anchor", anchor);
  op_.SetInput("label", label);
  op_.SetInput("cls_pred", cls_pred);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_MultiBoxTarget(const NDArray &anchor, const NDArray &label, const NDArray &cls_pred, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiBoxTarget");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(anchor);
  op_.AddInput(label);
  op_.AddInput(cls_pred);
  return op_.Invoke();
}

inline Symbol _contrib_MultiProposal(const std::string &symbol_name, const Symbol &cls_prob, const Symbol &bbox_pred, const Symbol &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiProposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_MultiProposal(const NDArray &cls_prob, const NDArray &bbox_pred, const NDArray &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_MultiProposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(cls_prob);
  op_.AddInput(bbox_pred);
  op_.AddInput(im_info);
  return op_.Invoke();
}

inline Symbol _contrib_PSROIPooling(const std::string &symbol_name, const Symbol &data, const Symbol &rois, double spatial_scale, int output_dim, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_PSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_PSROIPooling(const NDArray &data, const NDArray &rois, double spatial_scale, int output_dim, int pooled_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_PSROIPooling");
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("pooled_size", pooled_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(rois);
  return op_.Invoke();
}

inline Symbol _contrib_Proposal(const std::string &symbol_name, const Symbol &cls_prob, const Symbol &bbox_pred, const Symbol &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_Proposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_Proposal(const NDArray &cls_prob, const NDArray &bbox_pred, const NDArray &im_info, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_Proposal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(cls_prob);
  op_.AddInput(bbox_pred);
  op_.AddInput(im_info);
  return op_.Invoke();
}

inline Symbol _contrib_RotaryEmbedding(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_RotaryEmbedding");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_RotaryEmbedding(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_RotaryEmbedding");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _contrib_SSDScan(const std::string &symbol_name, const Symbol &data, const Symbol &dt, const Symbol &A_log, const Symbol &B, const Symbol &C, const Symbol &D, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_SSDScan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("dt", dt);
  op_.SetInput("A_log", A_log);
  op_.SetInput("B", B);
  op_.SetInput("C", C);
  op_.SetInput("D", D);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_SSDScan(const NDArray &data, const NDArray &dt, const NDArray &A_log, const NDArray &B, const NDArray &C, const NDArray &D, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_SSDScan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(dt);
  op_.AddInput(A_log);
  op_.AddInput(B);
  op_.AddInput(C);
  op_.AddInput(D);
  return op_.Invoke();
}

inline Symbol _contrib_count_sketch(const std::string &symbol_name, const Symbol &data, const Symbol &h, const Symbol &s, int out_dim, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_count_sketch");
  op_.SetParam("out_dim", out_dim);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("h", h);
  op_.SetInput("s", s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_count_sketch(const NDArray &data, const NDArray &h, const NDArray &s, int out_dim, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_count_sketch");
  op_.SetParam("out_dim", out_dim);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(h);
  op_.AddInput(s);
  return op_.Invoke();
}

inline Symbol _contrib_ctc_loss(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_ctc_loss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_ctc_loss(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_ctc_loss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol _contrib_dequantize(const std::string &symbol_name, const Symbol &data, const Symbol &min_range, const Symbol &max_range, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_dequantize");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("min_range", min_range);
  op_.SetInput("max_range", max_range);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_dequantize(const NDArray &data, const NDArray &min_range, const NDArray &max_range, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_dequantize");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(min_range);
  op_.AddInput(max_range);
  return op_.Invoke();
}

inline Symbol _contrib_fft(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_fft");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_fft(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_fft");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _contrib_ifft(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_ifft");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_ifft(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_ifft");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _contrib_krprod(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_krprod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_krprod(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_krprod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol _contrib_quantize(const std::string &symbol_name, const Symbol &data, const Symbol &min_range, const Symbol &max_range, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_quantize");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("min_range", min_range);
  op_.SetInput("max_range", max_range);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _contrib_quantize(const NDArray &data, const NDArray &min_range, const NDArray &max_range, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_contrib_quantize");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(min_range);
  op_.AddInput(max_range);
  return op_.Invoke();
}

inline Symbol _copy(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_copy");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _copy(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_copy");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _crop_assign(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_crop_assign");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _crop_assign(const NDArray &lhs, const NDArray &rhs, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_crop_assign");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _crop_assign_scalar(const std::string &symbol_name, const Symbol &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_crop_assign_scalar");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _crop_assign_scalar(const NDArray &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_crop_assign_scalar");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _div(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_div");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _div(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_div");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _div_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_div_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _div_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_div_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _equal_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _equal_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _full(const std::string &symbol_name, const Shape & shape, double value, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_full");
  op_.SetParam("shape", shape);
  op_.SetParam("value", value);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _full(const Shape & shape, double value, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_full");
  op_.SetParam("shape", shape);
  op_.SetParam("value", value);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _grad_add(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_grad_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _grad_add(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_grad_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _greater(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _greater(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _greater_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _greater_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _greater_equal_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _greater_equal_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _greater_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _greater_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_greater_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _hypot(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_hypot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _hypot(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_hypot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _hypot_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_hypot_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _hypot_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_hypot_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _identity_with_attr_like_rhs(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_identity_with_attr_like_rhs");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _identity_with_attr_like_rhs(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_identity_with_attr_like_rhs");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _khatri_rao(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_khatri_rao");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _khatri_rao(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_khatri_rao");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol _lesser(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _lesser(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _lesser_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _lesser_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _lesser_equal_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _lesser_equal_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _lesser_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _lesser_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_lesser_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _linalg_gelqf(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_gelqf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_gelqf(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_gelqf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol _linalg_gemm(const std::string &symbol_name, const Symbol &A, const Symbol &B, const Symbol &C, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_gemm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  op_.SetInput("C", C);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_gemm(const NDArray &A, const NDArray &B, const NDArray &C, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_gemm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  op_.AddInput(C);
  return op_.Invoke();
}

inline Symbol _linalg_gemm2(const std::string &symbol_name, const Symbol &A, const Symbol &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_gemm2");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_gemm2(const NDArray &A, const NDArray &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_gemm2");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  return op_.Invoke();
}

inline Symbol _linalg_potrf(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_potrf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_potrf(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_potrf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol _linalg_potri(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_potri");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_potri(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_potri");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol _linalg_sumlogdiag(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_sumlogdiag");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_sumlogdiag(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_sumlogdiag");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol _linalg_syrk(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_syrk");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_syrk(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_syrk");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol _linalg_trmm(const std::string &symbol_name, const Symbol &A, const Symbol &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_trmm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_trmm(const NDArray &A, const NDArray &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_trmm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  return op_.Invoke();
}

inline Symbol _linalg_trsm(const std::string &symbol_name, const Symbol &A, const Symbol &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_trsm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _linalg_trsm(const NDArray &A, const NDArray &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_linalg_trsm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  return op_.Invoke();
}

inline Symbol _maximum(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_maximum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _maximum(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_maximum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _maximum_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_maximum_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _maximum_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_maximum_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _minimum(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minimum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _minimum(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minimum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _minimum_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minimum_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _minimum_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minimum_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _minus(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _minus(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _minus_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minus_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _minus_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_minus_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _mod(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _mod(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _mod_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mod_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _mod_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mod_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _mul(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mul");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _mul(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mul");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _mul_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mul_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _mul_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_mul_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _not_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_not_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _not_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_not_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _not_equal_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_not_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _not_equal_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_not_equal_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _ones(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_ones");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _ones(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_ones");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _plus(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_plus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _plus(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_plus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _plus_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_plus_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _plus_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_plus_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _power(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_power");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _power(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_power");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _power_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_power_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _power_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_power_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _random_exponential(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_exponential");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_exponential(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_exponential");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_exponential(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_exponential");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_exponential(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_exponential");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_gamma(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_gamma");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_gamma(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_gamma");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_gamma(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_gamma");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_gamma(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_gamma");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_generalized_negative_binomial(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_generalized_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_generalized_negative_binomial(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_generalized_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_generalized_negative_binomial(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_generalized_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_generalized_negative_binomial(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_generalized_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_negative_binomial(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_negative_binomial(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_negative_binomial(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_negative_binomial(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_normal(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_normal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_normal(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_normal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_normal(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_normal");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_normal(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_normal");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_poisson(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_poisson");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_poisson(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_poisson");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_poisson(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_poisson");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_poisson(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_poisson");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_uniform(const std::string &symbol_name, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_uniform");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_uniform(const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_uniform");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _random_uniform(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_uniform");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _random_uniform(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_random_uniform");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol _rdiv_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rdiv_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _rdiv_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rdiv_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _rminus_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rminus_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _rminus_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rminus_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _rmod_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rmod_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _rmod_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rmod_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _rpower_scalar(const std::string &symbol_name, const Symbol &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rpower_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _rpower_scalar(const NDArray &data, double scalar, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_rpower_scalar");
  op_.SetParam("scalar", scalar);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _slice_assign(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_slice_assign");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _slice_assign(const NDArray &lhs, const NDArray &rhs, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_slice_assign");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _slice_assign_scalar(const std::string &symbol_name, const Symbol &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_slice_assign_scalar");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _slice_assign_scalar(const NDArray &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_slice_assign_scalar");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _square_sum(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_square_sum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _square_sum(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_square_sum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol _sub(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_sub");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _sub(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_sub");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol _sum(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_sum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _sum(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_sum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol _zeros(const std::string &symbol_name, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_zeros");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> _zeros(const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("_zeros");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  return op_.Invoke();
}

inline Symbol abs(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("abs");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> abs(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("abs");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol adam_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &mean, const Symbol &var, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("adam_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mean", mean);
  op_.SetInput("var", var);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> adam_update(const NDArray &weight, const NDArray &grad, const NDArray &mean, const NDArray &var, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("adam_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(mean);
  op_.AddInput(var);
  return op_.Invoke();
}

inline Symbol add_n(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("add_n");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> add_n(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("add_n");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol arccos(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arccos");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> arccos(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arccos");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol arccosh(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arccosh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> arccosh(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arccosh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol arcsin(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arcsin");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> arcsin(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arcsin");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol arcsinh(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arcsinh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> arcsinh(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arcsinh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol arctan(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arctan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> arctan(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arctan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol arctanh(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arctanh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> arctanh(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("arctanh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol argmax(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> argmax(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol argmax_channel(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argmax_channel");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> argmax_channel(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argmax_channel");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol argmin(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argmin");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> argmin(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argmin");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol argsort(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argsort");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> argsort(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("argsort");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol batch_dot(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("batch_dot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> batch_dot(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("batch_dot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol batch_take(const std::string &symbol_name, const Symbol &a, const Symbol &indices, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("batch_take");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("a", a);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> batch_take(const NDArray &a, const NDArray &indices, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("batch_take");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(a);
  op_.AddInput(indices);
  return op_.Invoke();
}

inline Symbol broadcast_add(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_add(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_axes(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_axes");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_axes(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_axes");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol broadcast_axis(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_axis");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_axis(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_axis");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol broadcast_div(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_div");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_div(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_div");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_greater(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_greater");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_greater(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_greater");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_greater_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_greater_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_greater_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_greater_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_hypot(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_hypot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_hypot(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_hypot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_lesser(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_lesser");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_lesser(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_lesser");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_lesser_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_lesser_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_lesser_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_lesser_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_maximum(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_maximum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_maximum(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_maximum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_minimum(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_minimum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_minimum(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_minimum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_minus(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_minus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_minus(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_minus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_mod(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_mod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_mod(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_mod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_mul(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_mul");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_mul(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_mul");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_not_equal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_not_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_not_equal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_not_equal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_plus(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_plus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_plus(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_plus");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_power(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_power");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_power(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_power");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_sub(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_sub");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_sub(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_sub");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol broadcast_to(const std::string &symbol_name, const Symbol &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_to");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> broadcast_to(const NDArray &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("broadcast_to");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol cast(const std::string &symbol_name, const Symbol &data, const std::string & dtype, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cast");
  op_.SetParam("dtype", dtype);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> cast(const NDArray &data, const std::string & dtype, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cast");
  op_.SetParam("dtype", dtype);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol cast_storage(const std::string &symbol_name, const Symbol &data, const std::string & stype, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cast_storage");
  op_.SetParam("stype", stype);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> cast_storage(const NDArray &data, const std::string & stype, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cast_storage");
  op_.SetParam("stype", stype);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol causal_conv1d(const std::string &symbol_name, const Symbol &data, const Symbol &weight, int kernel, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("causal_conv1d");
  op_.SetParam("kernel", kernel);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> causal_conv1d(const NDArray &data, const NDArray &weight, int kernel, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("causal_conv1d");
  op_.SetParam("kernel", kernel);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol cbrt(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cbrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> cbrt(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cbrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol ceil(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ceil");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ceil(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ceil");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol clip(const std::string &symbol_name, const Symbol &data, double a_min, double a_max, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("clip");
  op_.SetParam("a_min", a_min);
  op_.SetParam("a_max", a_max);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> clip(const NDArray &data, double a_min, double a_max, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("clip");
  op_.SetParam("a_min", a_min);
  op_.SetParam("a_max", a_max);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol concat(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("concat");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> concat(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("concat");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol cos(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cos");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> cos(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cos");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol cosh(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cosh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> cosh(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("cosh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol crop(const std::string &symbol_name, const Symbol &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("crop");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> crop(const NDArray &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("crop");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol ctc_loss(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ctc_loss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ctc_loss(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ctc_loss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol degrees(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("degrees");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> degrees(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("degrees");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol dequantize_int8(const std::string &symbol_name, const Symbol &data, const Shape & scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("dequantize_int8");
  op_.SetParam("scale", scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> dequantize_int8(const NDArray &data, const Shape & scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("dequantize_int8");
  op_.SetParam("scale", scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol dot(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("dot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> dot(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("dot");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol elemwise_add(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> elemwise_add(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_add");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol elemwise_div(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_div");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> elemwise_div(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_div");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol elemwise_mul(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_mul");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> elemwise_mul(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_mul");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol elemwise_sub(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_sub");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> elemwise_sub(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("elemwise_sub");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol erf(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("erf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> erf(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("erf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol exp(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("exp");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> exp(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("exp");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol expand_dims(const std::string &symbol_name, const Symbol &data, int axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("expand_dims");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> expand_dims(const NDArray &data, int axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("expand_dims");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol expm1(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("expm1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> expm1(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("expm1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol fix(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("fix");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> fix(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("fix");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol flash_attention(const std::string &symbol_name, const Symbol &query, const Symbol &key, const Symbol &value, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("flash_attention");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("query", query);
  op_.SetInput("key", key);
  op_.SetInput("value", value);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> flash_attention(const NDArray &query, const NDArray &key, const NDArray &value, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("flash_attention");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(query);
  op_.AddInput(key);
  op_.AddInput(value);
  return op_.Invoke();
}

inline Symbol flatten(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("flatten");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> flatten(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("flatten");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol flip(const std::string &symbol_name, const Symbol &data, const Shape & axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("flip");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> flip(const NDArray &data, const Shape & axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("flip");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol floor(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("floor");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> floor(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("floor");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol ftrl_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &z, const Symbol &n, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ftrl_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("z", z);
  op_.SetInput("n", n);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ftrl_update(const NDArray &weight, const NDArray &grad, const NDArray &z, const NDArray &n, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ftrl_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(z);
  op_.AddInput(n);
  return op_.Invoke();
}

inline Symbol gamma(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gamma");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> gamma(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gamma");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol gammaln(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gammaln");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> gammaln(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gammaln");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol gated_delta_rule(const std::string &symbol_name, const Symbol &query, const Symbol &key, const Symbol &value, const Symbol &g, const Symbol &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gated_delta_rule");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("query", query);
  op_.SetInput("key", key);
  op_.SetInput("value", value);
  op_.SetInput("g", g);
  op_.SetInput("beta", beta);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> gated_delta_rule(const NDArray &query, const NDArray &key, const NDArray &value, const NDArray &g, const NDArray &beta, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gated_delta_rule");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(query);
  op_.AddInput(key);
  op_.AddInput(value);
  op_.AddInput(g);
  op_.AddInput(beta);
  return op_.Invoke();
}

inline Symbol gather_nd(const std::string &symbol_name, const Symbol &data, const Symbol &indices, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gather_nd");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> gather_nd(const NDArray &data, const NDArray &indices, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("gather_nd");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(indices);
  return op_.Invoke();
}

inline Symbol head_gate(const std::string &symbol_name, const Symbol &data, const Symbol &gate, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("head_gate");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gate", gate);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> head_gate(const NDArray &data, const NDArray &gate, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("head_gate");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gate);
  return op_.Invoke();
}

inline Symbol head_norm_rotary(const std::string &symbol_name, const Symbol &data, const Symbol &gamma, int num_heads, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("head_norm_rotary");
  op_.SetParam("num_heads", num_heads);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> head_norm_rotary(const NDArray &data, const NDArray &gamma, int num_heads, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("head_norm_rotary");
  op_.SetParam("num_heads", num_heads);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(gamma);
  return op_.Invoke();
}

inline Symbol identity(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("identity");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> identity(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("identity");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol khatri_rao(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("khatri_rao");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> khatri_rao(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("khatri_rao");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol linalg_gelqf(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_gelqf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_gelqf(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_gelqf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol linalg_gemm(const std::string &symbol_name, const Symbol &A, const Symbol &B, const Symbol &C, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_gemm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  op_.SetInput("C", C);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_gemm(const NDArray &A, const NDArray &B, const NDArray &C, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_gemm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  op_.AddInput(C);
  return op_.Invoke();
}

inline Symbol linalg_gemm2(const std::string &symbol_name, const Symbol &A, const Symbol &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_gemm2");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_gemm2(const NDArray &A, const NDArray &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_gemm2");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  return op_.Invoke();
}

inline Symbol linalg_potrf(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_potrf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_potrf(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_potrf");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol linalg_potri(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_potri");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_potri(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_potri");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol linalg_sumlogdiag(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_sumlogdiag");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_sumlogdiag(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_sumlogdiag");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol linalg_syrk(const std::string &symbol_name, const Symbol &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_syrk");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_syrk(const NDArray &A, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_syrk");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  return op_.Invoke();
}

inline Symbol linalg_trmm(const std::string &symbol_name, const Symbol &A, const Symbol &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_trmm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_trmm(const NDArray &A, const NDArray &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_trmm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  return op_.Invoke();
}

inline Symbol linalg_trsm(const std::string &symbol_name, const Symbol &A, const Symbol &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_trsm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> linalg_trsm(const NDArray &A, const NDArray &B, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("linalg_trsm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(A);
  op_.AddInput(B);
  return op_.Invoke();
}

inline Symbol log(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> log(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol log10(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log10");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> log10(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log10");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol log1p(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log1p");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> log1p(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log1p");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol log2(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log2");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> log2(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log2");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol log_softmax(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log_softmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> log_softmax(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("log_softmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol make_loss(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("make_loss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> make_loss(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("make_loss");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol max(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("max");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> max(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("max");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol mean(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("mean");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> mean(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("mean");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol min(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("min");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> min(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("min");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol moe_experts(const std::string &symbol_name, const Symbol &data, const Symbol &topk_weight, const Symbol &topk_index, const Symbol &gate_weight, const Symbol &up_weight, const Symbol &down_weight, int num_experts, int experts_held, int hidden, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("moe_experts");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("experts_held", experts_held);
  op_.SetParam("hidden", hidden);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("topk_weight", topk_weight);
  op_.SetInput("topk_index", topk_index);
  op_.SetInput("gate_weight", gate_weight);
  op_.SetInput("up_weight", up_weight);
  op_.SetInput("down_weight", down_weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> moe_experts(const NDArray &data, const NDArray &topk_weight, const NDArray &topk_index, const NDArray &gate_weight, const NDArray &up_weight, const NDArray &down_weight, int num_experts, int experts_held, int hidden, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("moe_experts");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("experts_held", experts_held);
  op_.SetParam("hidden", hidden);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(topk_weight);
  op_.AddInput(topk_index);
  op_.AddInput(gate_weight);
  op_.AddInput(up_weight);
  op_.AddInput(down_weight);
  return op_.Invoke();
}

inline Symbol moe_router(const std::string &symbol_name, const Symbol &data, const Symbol &weight, int num_experts, int top_k, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("moe_router");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("top_k", top_k);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> moe_router(const NDArray &data, const NDArray &weight, int num_experts, int top_k, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("moe_router");
  op_.SetParam("num_experts", num_experts);
  op_.SetParam("top_k", top_k);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(weight);
  return op_.Invoke();
}

inline Symbol mp_sgd_mom_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &mom, const Symbol &weight32, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("mp_sgd_mom_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mom", mom);
  op_.SetInput("weight32", weight32);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> mp_sgd_mom_update(const NDArray &weight, const NDArray &grad, const NDArray &mom, const NDArray &weight32, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("mp_sgd_mom_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(mom);
  op_.AddInput(weight32);
  return op_.Invoke();
}

inline Symbol mp_sgd_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &weight32, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("mp_sgd_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("weight32", weight32);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> mp_sgd_update(const NDArray &weight, const NDArray &grad, const NDArray &weight32, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("mp_sgd_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(weight32);
  return op_.Invoke();
}

inline Symbol nanprod(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("nanprod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> nanprod(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("nanprod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol nansum(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("nansum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> nansum(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("nansum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol negative(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("negative");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> negative(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("negative");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol norm(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("norm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> norm(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("norm");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol one_hot(const std::string &symbol_name, const Symbol &data, int depth, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("one_hot");
  op_.SetParam("depth", depth);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> one_hot(const NDArray &data, int depth, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("one_hot");
  op_.SetParam("depth", depth);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol ones_like(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ones_like");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ones_like(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ones_like");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol pad(const std::string &symbol_name, const Symbol &data, const std::string & mode, const Shape & pad_width, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("pad");
  op_.SetParam("mode", mode);
  op_.SetParam("pad_width", pad_width);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> pad(const NDArray &data, const std::string & mode, const Shape & pad_width, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("pad");
  op_.SetParam("mode", mode);
  op_.SetParam("pad_width", pad_width);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol pick(const std::string &symbol_name, const Symbol &data, const Symbol &index, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("pick");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("index", index);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> pick(const NDArray &data, const NDArray &index, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("pick");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(index);
  return op_.Invoke();
}

inline Symbol prod(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("prod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> prod(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("prod");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol quantize_int8(const std::string &symbol_name, const Symbol &data, const Shape & scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("quantize_int8");
  op_.SetParam("scale", scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> quantize_int8(const NDArray &data, const Shape & scale, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("quantize_int8");
  op_.SetParam("scale", scale);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol radians(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("radians");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> radians(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("radians");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol rcbrt(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rcbrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> rcbrt(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rcbrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol reciprocal(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reciprocal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> reciprocal(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reciprocal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol relu(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("relu");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> relu(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("relu");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol repeat(const std::string &symbol_name, const Symbol &data, int repeats, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("repeat");
  op_.SetParam("repeats", repeats);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> repeat(const NDArray &data, int repeats, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("repeat");
  op_.SetParam("repeats", repeats);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol reshape(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reshape");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> reshape(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reshape");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol reshape(const std::string &symbol_name, const Symbol &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reshape");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> reshape(const NDArray &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reshape");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol reshape_like(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reshape_like");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> reshape_like(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reshape_like");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol reverse(const std::string &symbol_name, const Symbol &data, const Shape & axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reverse");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> reverse(const NDArray &data, const Shape & axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("reverse");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol rint(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rint");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> rint(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rint");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol rmsprop_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &n, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rmsprop_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("n", n);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> rmsprop_update(const NDArray &weight, const NDArray &grad, const NDArray &n, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rmsprop_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(n);
  return op_.Invoke();
}

inline Symbol rmspropalex_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &n, const Symbol &g, const Symbol &delta, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rmspropalex_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("n", n);
  op_.SetInput("g", g);
  op_.SetInput("delta", delta);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> rmspropalex_update(const NDArray &weight, const NDArray &grad, const NDArray &n, const NDArray &g, const NDArray &delta, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rmspropalex_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(n);
  op_.AddInput(g);
  op_.AddInput(delta);
  return op_.Invoke();
}

inline Symbol rotary_embedding(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rotary_embedding");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> rotary_embedding(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rotary_embedding");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol round(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("round");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> round(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("round");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol rsqrt(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rsqrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> rsqrt(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("rsqrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_exponential(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_exponential");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_exponential(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_exponential");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_exponential(const std::string &symbol_name, const Symbol &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_exponential");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_exponential(const NDArray &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_exponential");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_gamma(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_gamma");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_gamma(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_gamma");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_gamma(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_gamma");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_gamma(const NDArray &lhs, const NDArray &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_gamma");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_generalized_negative_binomial(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_generalized_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_generalized_negative_binomial(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_generalized_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_generalized_negative_binomial(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_generalized_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_generalized_negative_binomial(const NDArray &lhs, const NDArray &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_generalized_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_multinomial(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_multinomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_multinomial(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_multinomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_multinomial(const std::string &symbol_name, const Symbol &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_multinomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_multinomial(const NDArray &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_multinomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_negative_binomial(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_negative_binomial(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_negative_binomial");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_negative_binomial(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_negative_binomial(const NDArray &lhs, const NDArray &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_negative_binomial");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_normal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_normal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_normal(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_normal");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_normal(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_normal");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_normal(const NDArray &lhs, const NDArray &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_normal");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_poisson(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_poisson");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_poisson(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_poisson");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_poisson(const std::string &symbol_name, const Symbol &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_poisson");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_poisson(const NDArray &data, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_poisson");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sample_uniform(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_uniform");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_uniform(const NDArray &lhs, const NDArray &rhs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_uniform");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol sample_uniform(const std::string &symbol_name, const Symbol &lhs, const Symbol &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_uniform");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sample_uniform(const NDArray &lhs, const NDArray &rhs, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sample_uniform");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(lhs);
  op_.AddInput(rhs);
  return op_.Invoke();
}

inline Symbol scatter_nd(const std::string &symbol_name, const Symbol &data, const Symbol &indices, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("scatter_nd");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> scatter_nd(const NDArray &data, const NDArray &indices, const Shape & shape, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("scatter_nd");
  op_.SetParam("shape", shape);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(indices);
  return op_.Invoke();
}

inline Symbol sgd_mom_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, const Symbol &mom, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sgd_mom_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mom", mom);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sgd_mom_update(const NDArray &weight, const NDArray &grad, const NDArray &mom, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sgd_mom_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  op_.AddInput(mom);
  return op_.Invoke();
}

inline Symbol sgd_update(const std::string &symbol_name, const Symbol &weight, const Symbol &grad, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sgd_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sgd_update(const NDArray &weight, const NDArray &grad, double lr, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sgd_update");
  op_.SetParam("lr", lr);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(weight);
  op_.AddInput(grad);
  return op_.Invoke();
}

inline Symbol sigmoid(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sigmoid");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sigmoid(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sigmoid");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sign(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sign");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sign(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sign");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sin(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sin");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sin(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sin");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sinh(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sinh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sinh(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sinh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol slice(const std::string &symbol_name, const Symbol &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("slice");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> slice(const NDArray &data, const Shape & begin, const Shape & end, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("slice");
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol slice_axis(const std::string &symbol_name, const Symbol &data, int axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("slice_axis");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> slice_axis(const NDArray &data, int axis, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("slice_axis");
  op_.SetParam("axis", axis);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol smooth_l1(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("smooth_l1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> smooth_l1(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("smooth_l1");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol softmax(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("softmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> softmax(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("softmax");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol softmax_cross_entropy(const std::string &symbol_name, const Symbol &data, const Symbol &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("softmax_cross_entropy");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> softmax_cross_entropy(const NDArray &data, const NDArray &label, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("softmax_cross_entropy");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(label);
  return op_.Invoke();
}

inline Symbol softsign(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("softsign");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> softsign(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("softsign");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sort(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sort");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sort(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sort");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol space_to_depth(const std::string &symbol_name, const Symbol &data, int block_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("space_to_depth");
  op_.SetParam("block_size", block_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> space_to_depth(const NDArray &data, int block_size, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("space_to_depth");
  op_.SetParam("block_size", block_size);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol split(const std::string &symbol_name, const Symbol &data, int num_outputs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("split");
  op_.SetParam("num_outputs", num_outputs);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> split(const NDArray &data, int num_outputs, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("split");
  op_.SetParam("num_outputs", num_outputs);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sqrt(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sqrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sqrt(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sqrt");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol square(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("square");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> square(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("square");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol ssd_scan(const std::string &symbol_name, const Symbol &data, const Symbol &dt, const Symbol &A_log, const Symbol &B, const Symbol &C, const Symbol &D, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ssd_scan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  op_.SetInput("dt", dt);
  op_.SetInput("A_log", A_log);
  op_.SetInput("B", B);
  op_.SetInput("C", C);
  op_.SetInput("D", D);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> ssd_scan(const NDArray &data, const NDArray &dt, const NDArray &A_log, const NDArray &B, const NDArray &C, const NDArray &D, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("ssd_scan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  op_.AddInput(dt);
  op_.AddInput(A_log);
  op_.AddInput(B);
  op_.AddInput(C);
  op_.AddInput(D);
  return op_.Invoke();
}

inline Symbol stack(const std::string &symbol_name, const std::vector<Symbol> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("stack");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> stack(const std::vector<NDArray> &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("stack");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  for (const auto &a : data) op_.AddInput(a);
  return op_.Invoke();
}

inline Symbol stop_gradient(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("stop_gradient");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> stop_gradient(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("stop_gradient");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sum(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sum(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sum");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol sum_axis(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sum_axis");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> sum_axis(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("sum_axis");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol swapaxes(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("swapaxes");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> swapaxes(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("swapaxes");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol take(const std::string &symbol_name, const Symbol &a, const Symbol &indices, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("take");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("a", a);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> take(const NDArray &a, const NDArray &indices, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("take");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(a);
  op_.AddInput(indices);
  return op_.Invoke();
}

inline Symbol tan(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("tan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> tan(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("tan");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol tanh(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("tanh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> tanh(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("tanh");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol tile(const std::string &symbol_name, const Symbol &data, const Shape & reps, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("tile");
  op_.SetParam("reps", reps);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> tile(const NDArray &data, const Shape & reps, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("tile");
  op_.SetParam("reps", reps);
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol topk(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("topk");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> topk(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("topk");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol transpose(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("transpose");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> transpose(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("transpose");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol trunc(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("trunc");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> trunc(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("trunc");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

inline Symbol where(const std::string &symbol_name, const Symbol &condition, const Symbol &x, const Symbol &y, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("where");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("condition", condition);
  op_.SetInput("x", x);
  op_.SetInput("y", y);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> where(const NDArray &condition, const NDArray &x, const NDArray &y, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("where");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(condition);
  op_.AddInput(x);
  op_.AddInput(y);
  return op_.Invoke();
}

inline Symbol zeros_like(const std::string &symbol_name, const Symbol &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("zeros_like");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}
inline std::vector<NDArray> zeros_like(const NDArray &data, const std::map<std::string, std::string> &kwargs = {}) {
  Operator op_("zeros_like");
  for (const auto &kv : kwargs) op_.SetParam(kv.first, kv.second);
  op_.AddInput(data);
  return op_.Invoke();
}

}  // namespace op
}  // namespace cpp
}  // namespace mxtpu

#endif  // MXTPU_CPP_OP_H_
