/*
 * GENERATED by scala-package/generate_ops.py from doc/api_manifest.json
 * — do not edit. Regenerate after changing the operator registry:
 *     python ../tools/gen_api_manifest.py && python generate_ops.py
 */
package ml.dmlc.mxnet_tpu.gen

import ml.dmlc.mxnet_tpu.{NDArray, Symbol}

/** Typed creators for every registered operator. Each returns a composed
  * Symbol; `attrs` carries the operator parameters as strings (the ABI's
  * dmlc-style stringified params). */
object GeneratedOps {

  /** relu/sigmoid/tanh/softrelu (``activation-inl.h`` + mshadow_op.h),
    * params: act_type: str
    * inputs: data */
  def Activation(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Activation", name, attrs, inputs)

  /** Batch normalization (``batch_norm-inl.h``).
    * params: eps: float = 0.001; fix_gamma: bool = True; momentum: float = 0.9
    * inputs: data, gamma, beta */
  def BatchNorm(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("BatchNorm", name, attrs, inputs)

  /** Identity forward, zero gradient (``block_grad-inl.h``).
    * params: none
    * inputs: data */
  def BlockGrad(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("BlockGrad", name, attrs, inputs)

  /** Compressed convolutional attention (CCA; Zyphra,
    * params: head_dim: int; impl: str = flash; num_heads: int; num_kv_heads: int; rope_base: float = 10000.0; rotary_dim: int = 0
    * inputs: data, qk_weight, v_weight, conv0_weight, conv0_bias, conv1_weight, conv1_bias, temp, out_weight */
  def CCAttention(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("CCAttention", name, attrs, inputs)

  /** dtype conversion (``cast-inl.h``).
    * params: dtype: str
    * inputs: data */
  def Cast(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Cast", name, attrs, inputs)

  /** Concatenate along ``dim`` (``concat-inl.h``).
    * params: dim: int = 1; num_args: int
    * inputs: arg0 */
  def Concat(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Concat", name, attrs, inputs)

  /** 2-D convolution, NCHW (``convolution-inl.h``).
    * params: dilate: shape = [1, 1]; kernel: shape; no_bias: bool = False; num_filter: int; num_group: int = 1; pad: shape = [0, 0]; stride: shape = [1, 1]; workspace: int = 512
    * inputs: data, weight, bias */
  def Convolution(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Convolution", name, attrs, inputs)

  /** Spatial crop to explicit size or to a reference symbol's H/W
    * params: center_crop: bool = False; h_w: shape = [0, 0]; num_args: int = 1; offset: shape = [0, 0]
    * inputs: data */
  def Crop(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Crop", name, attrs, inputs)

  /** Transposed convolution (``deconvolution-inl.h``): the gradient of
    * params: kernel: shape; no_bias: bool = True; num_filter: int; num_group: int = 1; pad: shape = [0, 0]; stride: shape = [1, 1]; workspace: int = 512
    * inputs: data, weight */
  def Deconvolution(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Deconvolution", name, attrs, inputs)

  /** Inverted dropout (``dropout-inl.h``): train-time mask scaled by
    * params: p: float = 0.5
    * inputs: data */
  def Dropout(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Dropout", name, attrs, inputs)

  /** N-ary addition (``elementwise_sum-inl.h``); also what autodiff uses
    * params: num_args: int
    * inputs: arg0 */
  def ElementWiseSum(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("ElementWiseSum", name, attrs, inputs)

  /** Index lookup table (``embedding-inl.h``): data (N,) of indices →
    * params: input_dim: int; output_dim: int
    * inputs: data, weight */
  def Embedding(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Embedding", name, attrs, inputs)

  /** Collapse all but the batch dim (``reshape-inl.h`` FlattenProp).
    * params: none
    * inputs: data */
  def Flatten(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Flatten", name, attrs, inputs)

  /** out = data · weightᵀ + bias (``fully_connected-inl.h:53-81``).
    * params: flatten: bool = True; no_bias: bool = False; num_hidden: int; precision: str = 
    * inputs: data, weight, bias */
  def FullyConnected(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("FullyConnected", name, attrs, inputs)

  /** Causal grouped-query softmax attention with a head size of its
    * params: eps: float = 1e-06; head_dim: int; impl: str = flash; num_heads: int; num_kv_heads: int; rope_base: float = 10000.0; rotary_dim: int = 0
    * inputs: data, q_weight, k_weight, v_weight, q_norm, k_norm, out_weight */
  def GatedAttention(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("GatedAttention", name, attrs, inputs)

  /** A Gated DeltaNet mixer (linear attention; Yang et al.,
    * params: conv_kernel: int = 4; eps: float = 1e-06; head_k_dim: int; head_v_dim: int; num_k_heads: int; num_v_heads: int
    * inputs: data, qkvz_weight, ba_weight, conv_weight, a_log, dt_bias, norm_weight, out_weight */
  def GatedDeltaNet(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("GatedDeltaNet", name, attrs, inputs)

  /** The writing half of a hyper-connection: ``X <- H_res X +
    * params: lanes: int
    * inputs: data, branch, mix */
  def HyperConnectionPost(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("HyperConnectionPost", name, attrs, inputs)

  /** The reading half of a hyper-connection (the section above): from
    * params: clamp: float = 30.0; eps: float = 1e-06; iters: int = 20; lanes: int; norm_eps: float = 1e-06; res_diag: float = 0.0
    * inputs: data, phi, alpha, bias */
  def HyperConnectionPre(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("HyperConnectionPre", name, attrs, inputs)

  /** Identity forward that attaches a KL sparsity penalty gradient
    * params: momentum: float = 0.9; penalty: float = 0.001; sparseness_target: float = 0.1
    * inputs: data */
  def IdentityAttachKLSparseReg(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("IdentityAttachKLSparseReg", name, attrs, inputs)

  /** Local response normalization across channels (``lrn-inl.h``),
    * params: alpha: float = 0.0001; beta: float = 0.75; knorm: float = 2.0; nsize: int
    * inputs: data */
  def LRN(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("LRN", name, attrs, inputs)

  /** Causal multi-head latent attention (MLA), no biases. data
    * params: eps: float = 1e-06; impl: str = flash; kv_lora_rank: int; mscale_all_dim: float = 0.0; nope_dim: int; num_heads: int; q_lora_rank: int; rope_base: float = 10000.0; rope_dim: int; v_dim: int; yarn_beta_fast: float = 32.0; yarn_beta_slow: float = 1.0; yarn_factor: float = 0.0; yarn_original_max: int = 4096
    * inputs: data, q_down_weight, q_norm, q_up_weight, kv_down_weight, kv_norm, kv_up_weight, out_weight */
  def LatentAttention(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("LatentAttention", name, attrs, inputs)

  /** Layer normalization over the trailing axis: gamma/beta learnable.
    * params: eps: float = 1e-05
    * inputs: data, gamma, beta */
  def LayerNorm(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("LayerNorm", name, attrs, inputs)

  /** leaky/prelu/rrelu/elu (``leaky_relu-inl.h``). prelu learns a
    * params: act_type: str = leaky; lower_bound: float = 0.125; slope: float = 0.25; upper_bound: float = 0.334
    * inputs: data */
  def LeakyReLU(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("LeakyReLU", name, attrs, inputs)

  /** LinearRegressionOutput
    * params: grad_scale: float = 1.0
    * inputs: data, label */
  def LinearRegressionOutput(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("LinearRegressionOutput", name, attrs, inputs)

  /** LogisticRegressionOutput
    * params: grad_scale: float = 1.0
    * inputs: data, label */
  def LogisticRegressionOutput(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("LogisticRegressionOutput", name, attrs, inputs)

  /** MAERegressionOutput
    * params: grad_scale: float = 1.0
    * inputs: data, label */
  def MAERegressionOutput(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("MAERegressionOutput", name, attrs, inputs)

  /** A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as Granite 4.0-H's
    * params: chunk_size: int = 256; conv_kernel: int = 4; eps: float = 1e-05; head_dim: int; n_groups: int = 1; num_heads: int; state_size: int
    * inputs: data, in_weight, conv_weight, conv_bias, a_log, d_skip, dt_bias, norm_weight, out_weight */
  def Mamba2(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Mamba2", name, attrs, inputs)

  /** Mixture-of-experts position-wise FFN (soft or top-k routing).
    * params: expert_first: int = 0; experts_held: int = 0; gated: bool = False; hidden: int; latent: int = 0; num_experts: int; relu2: bool = False; renormalize: bool = False; route_scale: float = 1.0; router: str = linear; shared_gated: bool = True; shared_hidden: int = 0; top_k: int = 0
    * inputs: data, gate_weight, expert_w1, expert_b1, expert_w2, expert_b2 */
  def MoEFFN(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("MoEFFN", name, attrs, inputs)

  /** Multi-head self-attention with fused QKV projection.
    * params: axis_name: str = sp; causal: bool = True; dropout: float = 0.0; impl: str = flash; no_bias: bool = False; num_heads: int; num_kv_heads: int = 0; rope: bool = False; rope_base: float = 10000.0; scale: float = 0.0; window: int = 0
    * inputs: data, qkv_weight, qkv_bias, out_weight, out_bias */
  def MultiHeadAttention(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("MultiHeadAttention", name, attrs, inputs)

  /** max/avg/sum pooling (``pooling-inl.h``). Output size uses ceil
    * params: global_pool: bool = False; kernel: shape; pad: shape = [0, 0]; pool_type: str = max; stride: shape = [1, 1]
    * inputs: data */
  def Pooling(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Pooling", name, attrs, inputs)

  /** out = data + pos[None, :, :] — learned additive positional
    * params: none
    * inputs: data, pos */
  def PositionalEmbedding(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("PositionalEmbedding", name, attrs, inputs)

  /** Root-mean-square normalization over the trailing axis:
    * params: eps: float = 1e-05
    * inputs: data, gamma */
  def RMSNorm(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("RMSNorm", name, attrs, inputs)

  /** View change (``reshape-inl.h``). ``target_shape`` excludes batch
    * params: shape: shape = []; target_shape: shape = []
    * inputs: data */
  def Reshape(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("Reshape", name, attrs, inputs)

  /** A residual connection with learned per-channel scales:
    * params: affine: str = full
    * inputs: data, branch, data_bias, data_scale, branch_bias, branch_scale */
  def ResidualMerge(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("ResidualMerge", name, attrs, inputs)

  /** Split along an axis into num_outputs (``slice_channel-inl.h``);
    * params: axis: int = 1; num_outputs: int; squeeze_axis: bool = False
    * inputs: data */
  def SliceChannel(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("SliceChannel", name, attrs, inputs)

  /** Softmax as a differentiable layer (``softmax_activation-inl.h``);
    * params: mode: str = instance
    * inputs: data */
  def SoftmaxActivation(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("SoftmaxActivation", name, attrs, inputs)

  /** Fused softmax + cross-entropy loss head: per-example loss out.
    * params: grad_scale: float = 1.0; ignore_label: float = -1.0; use_ignore: bool = False
    * inputs: data, label */
  def SoftmaxCELoss(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("SoftmaxCELoss", name, attrs, inputs)

  /** Softmax forward + fused cross-entropy gradient
    * params: grad_scale: float = 1.0; ignore_label: float = -1.0; multi_output: bool = False; use_ignore: bool = False
    * inputs: data, label */
  def SoftmaxOutput(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("SoftmaxOutput", name, attrs, inputs)

  /** Rearrange spatial blocks into channels (NCHW):
    * params: block_size: int
    * inputs: data */
  def SpaceToDepth(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("SpaceToDepth", name, attrs, inputs)

  /** The ends of a several-lane residual stream. ``mode="copy"``:
    * params: lanes: int; mode: str
    * inputs: data */
  def StreamLanes(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("StreamLanes", name, attrs, inputs)

  /** Swap two axes (``swapaxis-inl.h``).
    * params: dim1: int = 0; dim2: int = 0
    * inputs: data */
  def SwapAxis(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("SwapAxis", name, attrs, inputs)

  /** Nearest or bilinear upsampling (``upsampling-inl.h``). nearest takes
    * params: multi_input_mode: str = concat; num_args: int = 1; num_filter: int = 0; sample_type: str = nearest; scale: int; workspace: int = 512
    * inputs: data */
  def UpSampling(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("UpSampling", name, attrs, inputs)

  /** _Div
    * params: none
    * inputs: lhs, rhs */
  def _Div(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Div", name, attrs, inputs)

  /** _DivScalar
    * params: scalar: float
    * inputs: data */
  def _DivScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_DivScalar", name, attrs, inputs)

  /** _Maximum
    * params: none
    * inputs: lhs, rhs */
  def _Maximum(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Maximum", name, attrs, inputs)

  /** _MaximumScalar
    * params: scalar: float
    * inputs: data */
  def _MaximumScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_MaximumScalar", name, attrs, inputs)

  /** _Minimum
    * params: none
    * inputs: lhs, rhs */
  def _Minimum(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Minimum", name, attrs, inputs)

  /** _MinimumScalar
    * params: scalar: float
    * inputs: data */
  def _MinimumScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_MinimumScalar", name, attrs, inputs)

  /** _Minus
    * params: none
    * inputs: lhs, rhs */
  def _Minus(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Minus", name, attrs, inputs)

  /** _MinusScalar
    * params: scalar: float
    * inputs: data */
  def _MinusScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_MinusScalar", name, attrs, inputs)

  /** _Mul
    * params: none
    * inputs: lhs, rhs */
  def _Mul(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Mul", name, attrs, inputs)

  /** _MulScalar
    * params: scalar: float
    * inputs: data */
  def _MulScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_MulScalar", name, attrs, inputs)

  /** _Plus
    * params: none
    * inputs: lhs, rhs */
  def _Plus(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Plus", name, attrs, inputs)

  /** _PlusScalar
    * params: scalar: float
    * inputs: data */
  def _PlusScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_PlusScalar", name, attrs, inputs)

  /** _Power
    * params: none
    * inputs: lhs, rhs */
  def _Power(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_Power", name, attrs, inputs)

  /** _PowerScalar
    * params: scalar: float
    * inputs: data */
  def _PowerScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_PowerScalar", name, attrs, inputs)

  /** _RDivScalar
    * params: scalar: float
    * inputs: data */
  def _RDivScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_RDivScalar", name, attrs, inputs)

  /** _RMinusScalar
    * params: scalar: float
    * inputs: data */
  def _RMinusScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_RMinusScalar", name, attrs, inputs)

  /** _RPowerScalar
    * params: scalar: float
    * inputs: data */
  def _RPowerScalar(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("_RPowerScalar", name, attrs, inputs)

  /** abs
    * params: none
    * inputs: data */
  def abs(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("abs", name, attrs, inputs)

  /** ceil
    * params: none
    * inputs: data */
  def ceil(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("ceil", name, attrs, inputs)

  /** cos
    * params: none
    * inputs: data */
  def cos(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("cos", name, attrs, inputs)

  /** exp
    * params: none
    * inputs: data */
  def exp(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("exp", name, attrs, inputs)

  /** floor
    * params: none
    * inputs: data */
  def floor(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("floor", name, attrs, inputs)

  /** log
    * params: none
    * inputs: data */
  def log(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("log", name, attrs, inputs)

  /** round
    * params: none
    * inputs: data */
  def round(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("round", name, attrs, inputs)

  /** rsqrt
    * params: none
    * inputs: data */
  def rsqrt(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("rsqrt", name, attrs, inputs)

  /** sign
    * params: none
    * inputs: data */
  def sign(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("sign", name, attrs, inputs)

  /** sin
    * params: none
    * inputs: data */
  def sin(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("sin", name, attrs, inputs)

  /** sqrt
    * params: none
    * inputs: data */
  def sqrt(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("sqrt", name, attrs, inputs)

  /** square
    * params: none
    * inputs: data */
  def square(name: String, inputs: Map[String, Symbol],
      attrs: Map[String, String] = Map.empty): Symbol =
    Symbol.createFromNamedArgs("square", name, attrs, inputs)

  // ---- NDArray registry functions (MXTFuncInvoke dispatch) ----
  /** _copyto */
  def _copyto(in0: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("_copyto", Array[NDArray](in0), Array[Float](), Array(out))
    out
  }

  /** _div */
  def _div(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("_div", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** _div_scalar */
  def _div_scalar(in0: NDArray, s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_div_scalar", Array[NDArray](in0), Array[Float](s0), Array(out))
    out
  }

  /** _minus */
  def _minus(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("_minus", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** _minus_scalar */
  def _minus_scalar(in0: NDArray, s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_minus_scalar", Array[NDArray](in0), Array[Float](s0), Array(out))
    out
  }

  /** _mul */
  def _mul(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("_mul", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** _mul_scalar */
  def _mul_scalar(in0: NDArray, s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_mul_scalar", Array[NDArray](in0), Array[Float](s0), Array(out))
    out
  }

  /** _onehot_encode */
  def _onehot_encode(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("_onehot_encode", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** _plus */
  def _plus(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("_plus", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** _plus_scalar */
  def _plus_scalar(in0: NDArray, s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_plus_scalar", Array[NDArray](in0), Array[Float](s0), Array(out))
    out
  }

  /** _random_gaussian */
  def _random_gaussian(s0: Float, s1: Float, out: NDArray): NDArray = {
    NDArray.invoke("_random_gaussian", Array[NDArray](), Array[Float](s0, s1), Array(out))
    out
  }

  /** _random_uniform */
  def _random_uniform(s0: Float, s1: Float, out: NDArray): NDArray = {
    NDArray.invoke("_random_uniform", Array[NDArray](), Array[Float](s0, s1), Array(out))
    out
  }

  /** _rdiv_scalar */
  def _rdiv_scalar(in0: NDArray, s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_rdiv_scalar", Array[NDArray](in0), Array[Float](s0), Array(out))
    out
  }

  /** _rminus_scalar */
  def _rminus_scalar(in0: NDArray, s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_rminus_scalar", Array[NDArray](in0), Array[Float](s0), Array(out))
    out
  }

  /** _set_value */
  def _set_value(s0: Float, out: NDArray): NDArray = {
    NDArray.invoke("_set_value", Array[NDArray](), Array[Float](s0), Array(out))
    out
  }

  /** choose_element_0index */
  def choose_element_0index(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("choose_element_0index", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** clip */
  def clip(in0: NDArray, s0: Float, s1: Float, out: NDArray): NDArray = {
    NDArray.invoke("clip", Array[NDArray](in0), Array[Float](s0, s1), Array(out))
    out
  }

  /** dot */
  def dot(in0: NDArray, in1: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("dot", Array[NDArray](in0, in1), Array[Float](), Array(out))
    out
  }

  /** fill_element_0index */
  def fill_element_0index(in0: NDArray, in1: NDArray, in2: NDArray, out: NDArray): NDArray = {
    NDArray.invoke("fill_element_0index", Array[NDArray](in0, in1, in2), Array[Float](), Array(out))
    out
  }

}
