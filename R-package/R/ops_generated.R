# GENERATED by R-package/generate_ops_r.py from
# doc/api_manifest.json — do not edit. Regenerate with:
#     python ../tools/gen_api_manifest.py && python generate_ops_r.py

#' relu/sigmoid/tanh/softrelu (``activation-inl.h`` + mshadow_op.h),
#'
#' Parameters: act_type (str)
#' Symbol inputs: data
#' @export
`mx.symbol.Activation` <- function(..., name = "") {
  mx.symbol.internal.create("Activation", name, list(...))
}

#' Batch normalization (``batch_norm-inl.h``).
#'
#' Parameters: eps (float, default 0.001); fix_gamma (bool, default True); momentum (float, default 0.9)
#' Symbol inputs: data, gamma, beta
#' @export
`mx.symbol.BatchNorm` <- function(..., name = "") {
  mx.symbol.internal.create("BatchNorm", name, list(...))
}

#' Identity forward, zero gradient (``block_grad-inl.h``).
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.BlockGrad` <- function(..., name = "") {
  mx.symbol.internal.create("BlockGrad", name, list(...))
}

#' Compressed convolutional attention (CCA; Zyphra,
#'
#' Parameters: head_dim (int); impl (str, default flash); num_heads (int); num_kv_heads (int); rope_base (float, default 10000.0); rotary_dim (int, default 0)
#' Symbol inputs: data, qk_weight, v_weight, conv0_weight, conv0_bias, conv1_weight, conv1_bias, temp, out_weight
#' @export
`mx.symbol.CCAttention` <- function(..., name = "") {
  mx.symbol.internal.create("CCAttention", name, list(...))
}

#' dtype conversion (``cast-inl.h``).
#'
#' Parameters: dtype (str)
#' Symbol inputs: data
#' @export
`mx.symbol.Cast` <- function(..., name = "") {
  mx.symbol.internal.create("Cast", name, list(...))
}

#' Concatenate along ``dim`` (``concat-inl.h``).
#'
#' Parameters: dim (int, default 1); num_args (int)
#' Symbol inputs: arg0
#' @export
`mx.symbol.Concat` <- function(..., name = "") {
  mx.symbol.internal.create("Concat", name, list(...))
}

#' 2-D convolution, NCHW (``convolution-inl.h``).
#'
#' Parameters: dilate (shape, default [1, 1]); kernel (shape); no_bias (bool, default False); num_filter (int); num_group (int, default 1); pad (shape, default [0, 0]); stride (shape, default [1, 1]); workspace (int, default 512)
#' Symbol inputs: data, weight, bias
#' @export
`mx.symbol.Convolution` <- function(..., name = "") {
  mx.symbol.internal.create("Convolution", name, list(...))
}

#' Spatial crop to explicit size or to a reference symbols H/W
#'
#' Parameters: center_crop (bool, default False); h_w (shape, default [0, 0]); num_args (int, default 1); offset (shape, default [0, 0])
#' Symbol inputs: data
#' @export
`mx.symbol.Crop` <- function(..., name = "") {
  mx.symbol.internal.create("Crop", name, list(...))
}

#' Transposed convolution (``deconvolution-inl.h``): the gradient of
#'
#' Parameters: kernel (shape); no_bias (bool, default True); num_filter (int); num_group (int, default 1); pad (shape, default [0, 0]); stride (shape, default [1, 1]); workspace (int, default 512)
#' Symbol inputs: data, weight
#' @export
`mx.symbol.Deconvolution` <- function(..., name = "") {
  mx.symbol.internal.create("Deconvolution", name, list(...))
}

#' Inverted dropout (``dropout-inl.h``): train-time mask scaled by
#'
#' Parameters: p (float, default 0.5)
#' Symbol inputs: data
#' @export
`mx.symbol.Dropout` <- function(..., name = "") {
  mx.symbol.internal.create("Dropout", name, list(...))
}

#' N-ary addition (``elementwise_sum-inl.h``); also what autodiff uses
#'
#' Parameters: num_args (int)
#' Symbol inputs: arg0
#' @export
`mx.symbol.ElementWiseSum` <- function(..., name = "") {
  mx.symbol.internal.create("ElementWiseSum", name, list(...))
}

#' Index lookup table (``embedding-inl.h``): data (N,) of indices →
#'
#' Parameters: input_dim (int); output_dim (int)
#' Symbol inputs: data, weight
#' @export
`mx.symbol.Embedding` <- function(..., name = "") {
  mx.symbol.internal.create("Embedding", name, list(...))
}

#' Collapse all but the batch dim (``reshape-inl.h`` FlattenProp).
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.Flatten` <- function(..., name = "") {
  mx.symbol.internal.create("Flatten", name, list(...))
}

#' out = data · weightᵀ + bias (``fully_connected-inl.h:53-81``).
#'
#' Parameters: flatten (bool, default True); no_bias (bool, default False); num_hidden (int); precision (str, default )
#' Symbol inputs: data, weight, bias
#' @export
`mx.symbol.FullyConnected` <- function(..., name = "") {
  mx.symbol.internal.create("FullyConnected", name, list(...))
}

#' Causal grouped-query softmax attention with a head size of its
#'
#' Parameters: eps (float, default 1e-06); head_dim (int); impl (str, default flash); num_heads (int); num_kv_heads (int); rope_base (float, default 10000.0); rotary_dim (int, default 0)
#' Symbol inputs: data, q_weight, k_weight, v_weight, q_norm, k_norm, out_weight
#' @export
`mx.symbol.GatedAttention` <- function(..., name = "") {
  mx.symbol.internal.create("GatedAttention", name, list(...))
}

#' A Gated DeltaNet mixer (linear attention; Yang et al.,
#'
#' Parameters: conv_kernel (int, default 4); eps (float, default 1e-06); head_k_dim (int); head_v_dim (int); num_k_heads (int); num_v_heads (int)
#' Symbol inputs: data, qkvz_weight, ba_weight, conv_weight, a_log, dt_bias, norm_weight, out_weight
#' @export
`mx.symbol.GatedDeltaNet` <- function(..., name = "") {
  mx.symbol.internal.create("GatedDeltaNet", name, list(...))
}

#' The writing half of a hyper-connection: ``X <- H_res X +
#'
#' Parameters: lanes (int)
#' Symbol inputs: data, branch, mix
#' @export
`mx.symbol.HyperConnectionPost` <- function(..., name = "") {
  mx.symbol.internal.create("HyperConnectionPost", name, list(...))
}

#' The reading half of a hyper-connection (the section above): from
#'
#' Parameters: clamp (float, default 30.0); eps (float, default 1e-06); iters (int, default 20); lanes (int); norm_eps (float, default 1e-06); res_diag (float, default 0.0)
#' Symbol inputs: data, phi, alpha, bias
#' @export
`mx.symbol.HyperConnectionPre` <- function(..., name = "") {
  mx.symbol.internal.create("HyperConnectionPre", name, list(...))
}

#' Identity forward that attaches a KL sparsity penalty gradient
#'
#' Parameters: momentum (float, default 0.9); penalty (float, default 0.001); sparseness_target (float, default 0.1)
#' Symbol inputs: data
#' @export
`mx.symbol.IdentityAttachKLSparseReg` <- function(..., name = "") {
  mx.symbol.internal.create("IdentityAttachKLSparseReg", name, list(...))
}

#' Local response normalization across channels (``lrn-inl.h``),
#'
#' Parameters: alpha (float, default 0.0001); beta (float, default 0.75); knorm (float, default 2.0); nsize (int)
#' Symbol inputs: data
#' @export
`mx.symbol.LRN` <- function(..., name = "") {
  mx.symbol.internal.create("LRN", name, list(...))
}

#' Causal multi-head latent attention (MLA), no biases. data
#'
#' Parameters: eps (float, default 1e-06); impl (str, default flash); kv_lora_rank (int); mscale_all_dim (float, default 0.0); nope_dim (int); num_heads (int); q_lora_rank (int); rope_base (float, default 10000.0); rope_dim (int); v_dim (int); yarn_beta_fast (float, default 32.0); yarn_beta_slow (float, default 1.0); yarn_factor (float, default 0.0); yarn_original_max (int, default 4096)
#' Symbol inputs: data, q_down_weight, q_norm, q_up_weight, kv_down_weight, kv_norm, kv_up_weight, out_weight
#' @export
`mx.symbol.LatentAttention` <- function(..., name = "") {
  mx.symbol.internal.create("LatentAttention", name, list(...))
}

#' Layer normalization over the trailing axis: gamma/beta learnable.
#'
#' Parameters: eps (float, default 1e-05)
#' Symbol inputs: data, gamma, beta
#' @export
`mx.symbol.LayerNorm` <- function(..., name = "") {
  mx.symbol.internal.create("LayerNorm", name, list(...))
}

#' leaky/prelu/rrelu/elu (``leaky_relu-inl.h``). prelu learns a
#'
#' Parameters: act_type (str, default leaky); lower_bound (float, default 0.125); slope (float, default 0.25); upper_bound (float, default 0.334)
#' Symbol inputs: data
#' @export
`mx.symbol.LeakyReLU` <- function(..., name = "") {
  mx.symbol.internal.create("LeakyReLU", name, list(...))
}

#' LinearRegressionOutput
#'
#' Parameters: grad_scale (float, default 1.0)
#' Symbol inputs: data, label
#' @export
`mx.symbol.LinearRegressionOutput` <- function(..., name = "") {
  mx.symbol.internal.create("LinearRegressionOutput", name, list(...))
}

#' LogisticRegressionOutput
#'
#' Parameters: grad_scale (float, default 1.0)
#' Symbol inputs: data, label
#' @export
`mx.symbol.LogisticRegressionOutput` <- function(..., name = "") {
  mx.symbol.internal.create("LogisticRegressionOutput", name, list(...))
}

#' MAERegressionOutput
#'
#' Parameters: grad_scale (float, default 1.0)
#' Symbol inputs: data, label
#' @export
`mx.symbol.MAERegressionOutput` <- function(..., name = "") {
  mx.symbol.internal.create("MAERegressionOutput", name, list(...))
}

#' A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as Granite 4.0-Hs
#'
#' Parameters: chunk_size (int, default 256); conv_kernel (int, default 4); eps (float, default 1e-05); head_dim (int); n_groups (int, default 1); num_heads (int); state_size (int)
#' Symbol inputs: data, in_weight, conv_weight, conv_bias, a_log, d_skip, dt_bias, norm_weight, out_weight
#' @export
`mx.symbol.Mamba2` <- function(..., name = "") {
  mx.symbol.internal.create("Mamba2", name, list(...))
}

#' Mixture-of-experts position-wise FFN (soft or top-k routing).
#'
#' Parameters: expert_first (int, default 0); experts_held (int, default 0); gated (bool, default False); hidden (int); latent (int, default 0); num_experts (int); relu2 (bool, default False); renormalize (bool, default False); route_scale (float, default 1.0); router (str, default linear); shared_gated (bool, default True); shared_hidden (int, default 0); top_k (int, default 0)
#' Symbol inputs: data, gate_weight, expert_w1, expert_b1, expert_w2, expert_b2
#' @export
`mx.symbol.MoEFFN` <- function(..., name = "") {
  mx.symbol.internal.create("MoEFFN", name, list(...))
}

#' Multi-head self-attention with fused QKV projection.
#'
#' Parameters: axis_name (str, default sp); causal (bool, default True); dropout (float, default 0.0); impl (str, default flash); no_bias (bool, default False); num_heads (int); num_kv_heads (int, default 0); rope (bool, default False); rope_base (float, default 10000.0); scale (float, default 0.0); window (int, default 0)
#' Symbol inputs: data, qkv_weight, qkv_bias, out_weight, out_bias
#' @export
`mx.symbol.MultiHeadAttention` <- function(..., name = "") {
  mx.symbol.internal.create("MultiHeadAttention", name, list(...))
}

#' max/avg/sum pooling (``pooling-inl.h``). Output size uses ceil
#'
#' Parameters: global_pool (bool, default False); kernel (shape); pad (shape, default [0, 0]); pool_type (str, default max); stride (shape, default [1, 1])
#' Symbol inputs: data
#' @export
`mx.symbol.Pooling` <- function(..., name = "") {
  mx.symbol.internal.create("Pooling", name, list(...))
}

#' out = data + pos[None, :, :] — learned additive positional
#'
#' Parameters: none
#' Symbol inputs: data, pos
#' @export
`mx.symbol.PositionalEmbedding` <- function(..., name = "") {
  mx.symbol.internal.create("PositionalEmbedding", name, list(...))
}

#' Root-mean-square normalization over the trailing axis:
#'
#' Parameters: eps (float, default 1e-05)
#' Symbol inputs: data, gamma
#' @export
`mx.symbol.RMSNorm` <- function(..., name = "") {
  mx.symbol.internal.create("RMSNorm", name, list(...))
}

#' View change (``reshape-inl.h``). ``target_shape`` excludes batch
#'
#' Parameters: shape (shape, default []); target_shape (shape, default [])
#' Symbol inputs: data
#' @export
`mx.symbol.Reshape` <- function(..., name = "") {
  mx.symbol.internal.create("Reshape", name, list(...))
}

#' A residual connection with learned per-channel scales:
#'
#' Parameters: affine (str, default full)
#' Symbol inputs: data, branch, data_bias, data_scale, branch_bias, branch_scale
#' @export
`mx.symbol.ResidualMerge` <- function(..., name = "") {
  mx.symbol.internal.create("ResidualMerge", name, list(...))
}

#' Split along an axis into num_outputs (``slice_channel-inl.h``);
#'
#' Parameters: axis (int, default 1); num_outputs (int); squeeze_axis (bool, default False)
#' Symbol inputs: data
#' @export
`mx.symbol.SliceChannel` <- function(..., name = "") {
  mx.symbol.internal.create("SliceChannel", name, list(...))
}

#' Softmax as a differentiable layer (``softmax_activation-inl.h``);
#'
#' Parameters: mode (str, default instance)
#' Symbol inputs: data
#' @export
`mx.symbol.SoftmaxActivation` <- function(..., name = "") {
  mx.symbol.internal.create("SoftmaxActivation", name, list(...))
}

#' Fused softmax + cross-entropy loss head: per-example loss out.
#'
#' Parameters: grad_scale (float, default 1.0); ignore_label (float, default -1.0); use_ignore (bool, default False)
#' Symbol inputs: data, label
#' @export
`mx.symbol.SoftmaxCELoss` <- function(..., name = "") {
  mx.symbol.internal.create("SoftmaxCELoss", name, list(...))
}

#' Softmax forward + fused cross-entropy gradient
#'
#' Parameters: grad_scale (float, default 1.0); ignore_label (float, default -1.0); multi_output (bool, default False); use_ignore (bool, default False)
#' Symbol inputs: data, label
#' @export
`mx.symbol.SoftmaxOutput` <- function(..., name = "") {
  mx.symbol.internal.create("SoftmaxOutput", name, list(...))
}

#' Rearrange spatial blocks into channels (NCHW):
#'
#' Parameters: block_size (int)
#' Symbol inputs: data
#' @export
`mx.symbol.SpaceToDepth` <- function(..., name = "") {
  mx.symbol.internal.create("SpaceToDepth", name, list(...))
}

#' The ends of a several-lane residual stream. ``mode="copy"``:
#'
#' Parameters: lanes (int); mode (str)
#' Symbol inputs: data
#' @export
`mx.symbol.StreamLanes` <- function(..., name = "") {
  mx.symbol.internal.create("StreamLanes", name, list(...))
}

#' Swap two axes (``swapaxis-inl.h``).
#'
#' Parameters: dim1 (int, default 0); dim2 (int, default 0)
#' Symbol inputs: data
#' @export
`mx.symbol.SwapAxis` <- function(..., name = "") {
  mx.symbol.internal.create("SwapAxis", name, list(...))
}

#' Nearest or bilinear upsampling (``upsampling-inl.h``). nearest takes
#'
#' Parameters: multi_input_mode (str, default concat); num_args (int, default 1); num_filter (int, default 0); sample_type (str, default nearest); scale (int); workspace (int, default 512)
#' Symbol inputs: data
#' @export
`mx.symbol.UpSampling` <- function(..., name = "") {
  mx.symbol.internal.create("UpSampling", name, list(...))
}

#' _Div
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Div` <- function(..., name = "") {
  mx.symbol.internal.create("_Div", name, list(...))
}

#' _DivScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._DivScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_DivScalar", name, list(...))
}

#' _Maximum
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Maximum` <- function(..., name = "") {
  mx.symbol.internal.create("_Maximum", name, list(...))
}

#' _MaximumScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._MaximumScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_MaximumScalar", name, list(...))
}

#' _Minimum
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Minimum` <- function(..., name = "") {
  mx.symbol.internal.create("_Minimum", name, list(...))
}

#' _MinimumScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._MinimumScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_MinimumScalar", name, list(...))
}

#' _Minus
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Minus` <- function(..., name = "") {
  mx.symbol.internal.create("_Minus", name, list(...))
}

#' _MinusScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._MinusScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_MinusScalar", name, list(...))
}

#' _Mul
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Mul` <- function(..., name = "") {
  mx.symbol.internal.create("_Mul", name, list(...))
}

#' _MulScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._MulScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_MulScalar", name, list(...))
}

#' _Plus
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Plus` <- function(..., name = "") {
  mx.symbol.internal.create("_Plus", name, list(...))
}

#' _PlusScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._PlusScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_PlusScalar", name, list(...))
}

#' _Power
#'
#' Parameters: none
#' Symbol inputs: lhs, rhs
#' @export
`mx.symbol._Power` <- function(..., name = "") {
  mx.symbol.internal.create("_Power", name, list(...))
}

#' _PowerScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._PowerScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_PowerScalar", name, list(...))
}

#' _RDivScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._RDivScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_RDivScalar", name, list(...))
}

#' _RMinusScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._RMinusScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_RMinusScalar", name, list(...))
}

#' _RPowerScalar
#'
#' Parameters: scalar (float)
#' Symbol inputs: data
#' @export
`mx.symbol._RPowerScalar` <- function(..., name = "") {
  mx.symbol.internal.create("_RPowerScalar", name, list(...))
}

#' abs
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.abs` <- function(..., name = "") {
  mx.symbol.internal.create("abs", name, list(...))
}

#' ceil
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.ceil` <- function(..., name = "") {
  mx.symbol.internal.create("ceil", name, list(...))
}

#' cos
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.cos` <- function(..., name = "") {
  mx.symbol.internal.create("cos", name, list(...))
}

#' exp
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.exp` <- function(..., name = "") {
  mx.symbol.internal.create("exp", name, list(...))
}

#' floor
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.floor` <- function(..., name = "") {
  mx.symbol.internal.create("floor", name, list(...))
}

#' log
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.log` <- function(..., name = "") {
  mx.symbol.internal.create("log", name, list(...))
}

#' round
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.round` <- function(..., name = "") {
  mx.symbol.internal.create("round", name, list(...))
}

#' rsqrt
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.rsqrt` <- function(..., name = "") {
  mx.symbol.internal.create("rsqrt", name, list(...))
}

#' sign
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.sign` <- function(..., name = "") {
  mx.symbol.internal.create("sign", name, list(...))
}

#' sin
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.sin` <- function(..., name = "") {
  mx.symbol.internal.create("sin", name, list(...))
}

#' sqrt
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.sqrt` <- function(..., name = "") {
  mx.symbol.internal.create("sqrt", name, list(...))
}

#' square
#'
#' Parameters: none
#' Symbol inputs: data
#' @export
`mx.symbol.square` <- function(..., name = "") {
  mx.symbol.internal.create("square", name, list(...))
}
