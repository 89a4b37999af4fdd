package AI::MXTpu::Ops;

# GENERATED FILE - do not edit; run perl-package/scripts/gen_op_pm.py.
#
# One sub per operator in the live registry (389 ops), each a
# thin funnel into AI::MXTpu::op("<name>", @inputs, %params) - the
# imperative-invoke path of the C ABI. Names shadowing Perl builtins
# carry a trailing underscore (relu is relu, but abs is abs_).
#
# ref: perl-package/AI-MXNet/lib/AI/MXNet/NDArray.pm autogenerates the
# same surface at runtime from MXListAllOpNames.

use strict;
use warnings;

use AI::MXTpu;

# Activation(x, act_type='relu')
sub Activation { AI::MXTpu::op('Activation', @_) }

# AdaptiveAvgPooling2D(data, output_size=(1, 1))
sub AdaptiveAvgPooling2D { AI::MXTpu::op('AdaptiveAvgPooling2D', @_) }

# BatchNorm(x, gamma, beta, moving_mean, moving_var, eps=0.001, momentum=0.9, fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1, cudnn_off=False, min_calib_range=None, max_calib_range=None, _training=True)
sub BatchNorm { AI::MXTpu::op('BatchNorm', @_) }

# BatchNorm_v1(x, gamma, beta, moving_mean, moving_var, eps=0.001, momentum=0.9, fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1, cudnn_off=False, min_calib_range=None, max_calib_range=None, _training=True)
sub BatchNorm_v1 { AI::MXTpu::op('BatchNorm_v1', @_) }

# BilinearResize2D(data, height=1, width=1, scale_height=None, scale_width=None, mode='size')
sub BilinearResize2D { AI::MXTpu::op('BilinearResize2D', @_) }

# BilinearSampler(data, grid, cudnn_off=False)
sub BilinearSampler { AI::MXTpu::op('BilinearSampler', @_) }

# BlockGrad(x)
sub BlockGrad { AI::MXTpu::op('BlockGrad', @_) }

# BlockGrad_inner(x)
sub BlockGrad_inner { AI::MXTpu::op('BlockGrad_inner', @_) }

# CTCLoss(pred, label, pred_lengths=None, label_lengths=None, layout='NTC', label_layout='NT')
sub CTCLoss { AI::MXTpu::op('CTCLoss', @_) }

# Cast(x, dtype='float32')
sub Cast { AI::MXTpu::op('Cast', @_) }

# Concat(*xs, dim=1)
sub Concat { AI::MXTpu::op('Concat', @_) }

# Convolution(x, weight, bias=None, kernel=None, stride=None, dilate=None, pad=None, num_filter=None, num_group=1, no_bias=False, layout='NCHW', cudnn_tune=None, cudnn_off=False, workspace=1024, precision=None)
sub Convolution { AI::MXTpu::op('Convolution', @_) }

# Convolution_v1(x, weight, bias=None, kernel=None, stride=None, dilate=None, pad=None, num_filter=None, num_group=1, no_bias=False, layout='NCHW', cudnn_tune=None, cudnn_off=False, workspace=1024, precision=None)
sub Convolution_v1 { AI::MXTpu::op('Convolution_v1', @_) }

# Correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1, stride2=1, pad_size=0, is_multiply=True)
sub Correlation { AI::MXTpu::op('Correlation', @_) }

# Crop(data, *crop_like, num_args=1, offset=(0, 0), h_w=(0, 0), center_crop=False)
sub Crop { AI::MXTpu::op('Crop', @_) }

# CuDNNBatchNorm(x, gamma, beta, moving_mean, moving_var, eps=0.001, momentum=0.9, fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1, cudnn_off=False, min_calib_range=None, max_calib_range=None, _training=True)
sub CuDNNBatchNorm { AI::MXTpu::op('CuDNNBatchNorm', @_) }

# Deconvolution(x, weight, bias=None, kernel=None, stride=None, dilate=None, pad=None, adj=None, target_shape=None, num_filter=None, num_group=1, no_bias=True, layout='NCHW', cudnn_tune=None, cudnn_off=False, workspace=512, precision=None)
sub Deconvolution { AI::MXTpu::op('Deconvolution', @_) }

# DeformableConvolution(data, offset, weight, bias=None, kernel=(3, 3), stride=(1, 1), dilate=(1, 1), pad=(0, 0), num_filter=1, num_group=1, num_deformable_group=1, no_bias=False, workspace=1024, layout=None)
sub DeformableConvolution { AI::MXTpu::op('DeformableConvolution', @_) }

# DeformablePSROIPooling(data, rois, trans=None, spatial_scale=1.0, output_dim=1, group_size=1, pooled_size=1, part_size=0, sample_per_part=1, trans_std=0.0, no_trans=False)
sub DeformablePSROIPooling { AI::MXTpu::op('DeformablePSROIPooling', @_) }

# Dropout(x, key=None, p=0.5, mode='training', axes=(), _training=True, cudnn_off=False)
sub Dropout { AI::MXTpu::op('Dropout', @_) }

# ElementWiseSum(*xs)
sub ElementWiseSum { AI::MXTpu::op('ElementWiseSum', @_) }

# Embedding(data, weight, input_dim=None, output_dim=None, dtype=None, sparse_grad=False)
sub Embedding { AI::MXTpu::op('Embedding', @_) }

# Flatten(x)
sub Flatten { AI::MXTpu::op('Flatten', @_) }

# FullyConnected(x, weight, bias=None, num_hidden=None, no_bias=False, flatten=True, precision=None)
sub FullyConnected { AI::MXTpu::op('FullyConnected', @_) }

# GridGenerator(data, transform_type='affine', target_shape=(0, 0))
sub GridGenerator { AI::MXTpu::op('GridGenerator', @_) }

# GroupNorm(x, gamma, beta, num_groups=1, eps=1e-05)
sub GroupNorm { AI::MXTpu::op('GroupNorm', @_) }

# IdentityAttachKLSparseReg(data, sparseness_target=0.1, penalty=0.001, momentum=0.9)
sub IdentityAttachKLSparseReg { AI::MXTpu::op('IdentityAttachKLSparseReg', @_) }

# InstanceNorm(x, gamma, beta, eps=0.001)
sub InstanceNorm { AI::MXTpu::op('InstanceNorm', @_) }

# L2Normalization(x, eps=1e-10, mode='instance')
sub L2Normalization { AI::MXTpu::op('L2Normalization', @_) }

# LRN(x, alpha=0.0001, beta=0.75, knorm=2.0, nsize=5)
sub LRN { AI::MXTpu::op('LRN', @_) }

# LayerNorm(x, gamma, beta, axis=-1, eps=1e-05, output_mean_var=False)
sub LayerNorm { AI::MXTpu::op('LayerNorm', @_) }

# LeakyReLU(x, gamma=None, act_type='leaky', slope=0.25, lower_bound=0.125, upper_bound=0.334)
sub LeakyReLU { AI::MXTpu::op('LeakyReLU', @_) }

# LinearRegressionOutput(data, label, grad_scale=1.0)
sub LinearRegressionOutput { AI::MXTpu::op('LinearRegressionOutput', @_) }

# LogisticRegressionOutput(data, label, grad_scale=1.0)
sub LogisticRegressionOutput { AI::MXTpu::op('LogisticRegressionOutput', @_) }

# MAERegressionOutput(data, label, grad_scale=1.0)
sub MAERegressionOutput { AI::MXTpu::op('MAERegressionOutput', @_) }

# MakeLoss(x, grad_scale=1.0, valid_thresh=0.0, normalization='null')
sub MakeLoss { AI::MXTpu::op('MakeLoss', @_) }

# MultiBoxDetection(cls_pred, loc_pred, anchors, clip=True, threshold=0.01, background_id=0, nms_threshold=0.5, force_suppress=False, variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1)
sub MultiBoxDetection { AI::MXTpu::op('MultiBoxDetection', @_) }

# MultiBoxPrior(data, sizes=(1.0,), ratios=(1.0,), clip=False, steps=(-1.0, -1.0), offsets=(0.5, 0.5))
sub MultiBoxPrior { AI::MXTpu::op('MultiBoxPrior', @_) }

# MultiBoxTarget(anchor, label, cls_pred, overlap_threshold=0.5, ignore_label=-1.0, negative_mining_ratio=-1.0, negative_mining_thresh=0.5, minimum_negative_samples=0, variances=(0.1, 0.1, 0.2, 0.2))
sub MultiBoxTarget { AI::MXTpu::op('MultiBoxTarget', @_) }

# MultiProposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000, rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16, scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16, output_score=False, iou_loss=False)
sub MultiProposal { AI::MXTpu::op('MultiProposal', @_) }

# PSROIPooling(data, rois, spatial_scale=1.0, output_dim=1, pooled_size=1, group_size=0)
sub PSROIPooling { AI::MXTpu::op('PSROIPooling', @_) }

# Pad(x, mode='constant', pad_width=(), constant_value=0.0)
sub Pad { AI::MXTpu::op('Pad', @_) }

# Pooling(x, kernel=None, pool_type='max', stride=None, pad=None, global_pool=False, pooling_convention='valid', cudnn_off=False, p_value=2, count_include_pad=True, layout=None)
sub Pooling { AI::MXTpu::op('Pooling', @_) }

# Pooling_v1(x, kernel=None, pool_type='max', stride=None, pad=None, global_pool=False, pooling_convention='valid', cudnn_off=False, p_value=2, count_include_pad=True, layout=None)
sub Pooling_v1 { AI::MXTpu::op('Pooling_v1', @_) }

# Proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000, rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16, scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16, output_score=False, iou_loss=False)
sub Proposal { AI::MXTpu::op('Proposal', @_) }

# RNN(data, parameters, state, state_cell=None, sequence_length=None, key=None, *, mode='lstm', state_size=None, num_layers=1, bidirectional=False, p=0.0, state_outputs=False, projection_size=None, lstm_state_clip_min=None, lstm_state_clip_max=None, lstm_state_clip_nan=False, use_sequence_length=False, _training=True)
sub RNN { AI::MXTpu::op('RNN', @_) }

# ROIAlign(data, rois, pooled_size=(7, 7), spatial_scale=1.0, sample_ratio=-1, position_sensitive=False, aligned=False)
sub ROIAlign { AI::MXTpu::op('ROIAlign', @_) }

# ROIPooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0)
sub ROIPooling { AI::MXTpu::op('ROIPooling', @_) }

# RROIAlign(data, rois, pooled_size=(1, 1), spatial_scale=1.0, sampling_ratio=-1)
sub RROIAlign { AI::MXTpu::op('RROIAlign', @_) }

# Reshape(x, shape=None, reverse=False)
sub Reshape { AI::MXTpu::op('Reshape', @_) }

# SVMOutput(data, label, margin=1.0, regularization_coefficient=1.0, use_linear=False)
sub SVMOutput { AI::MXTpu::op('SVMOutput', @_) }

# SequenceLast(data, sequence_length=None, use_sequence_length=True, axis=0)
sub SequenceLast { AI::MXTpu::op('SequenceLast', @_) }

# SequenceMask(data, sequence_length=None, use_sequence_length=True, value=0.0, axis=0)
sub SequenceMask { AI::MXTpu::op('SequenceMask', @_) }

# SequenceReverse(data, sequence_length=None, use_sequence_length=True, axis=0)
sub SequenceReverse { AI::MXTpu::op('SequenceReverse', @_) }

# SliceChannel(x, num_outputs=1, axis=1, squeeze_axis=False)
sub SliceChannel { AI::MXTpu::op('SliceChannel', @_) }

# SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1.0, multi_output=False, use_ignore=False, preserve_shape=False, normalization='null', out_grad=False, smooth_alpha=0.0)
sub SoftmaxOutput { AI::MXTpu::op('SoftmaxOutput', @_) }

# SpatialTransformer(data, loc, target_shape=(0, 0), transform_type='affine', sampler_type='bilinear', cudnn_off=None)
sub SpatialTransformer { AI::MXTpu::op('SpatialTransformer', @_) }

# SwapAxis(x, dim1=0, dim2=0)
sub SwapAxis { AI::MXTpu::op('SwapAxis', @_) }

# SyncBatchNorm(x, gamma, beta, moving_mean, moving_var, eps=0.001, momentum=0.9, fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1, cudnn_off=False, min_calib_range=None, max_calib_range=None, _training=True)
sub SyncBatchNorm { AI::MXTpu::op('SyncBatchNorm', @_) }

# UpSampling(*data, scale=1, sample_type='nearest', num_args=1, num_filter=0, multi_input_mode='concat', workspace=512)
sub UpSampling { AI::MXTpu::op('UpSampling', @_) }

# abs(...)
sub abs_ { AI::MXTpu::op('abs', @_) }

# activation(x, act_type='relu')
sub activation { AI::MXTpu::op('activation', @_) }

# adam_update(weight, grad, mean, var, lr=None, beta1=0.9, beta2=0.999, epsilon=1e-08, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True)
sub adam_update { AI::MXTpu::op('adam_update', @_) }

# adamw_update(weight, grad, mean, var, rescale_grad=1.0, lr=None, eta=None, beta1=0.9, beta2=0.999, epsilon=1e-08, wd=0.0, clip_gradient=-1.0)
sub adamw_update { AI::MXTpu::op('adamw_update', @_) }

# adaptive_avg_pooling_2d(data, output_size=(1, 1))
sub adaptive_avg_pooling_2d { AI::MXTpu::op('adaptive_avg_pooling_2d', @_) }

# add(...)
sub add { AI::MXTpu::op('add', @_) }

# add_n(*xs)
sub add_n { AI::MXTpu::op('add_n', @_) }

# all_finite(data, init_output=True)
sub all_finite { AI::MXTpu::op('all_finite', @_) }

# amp_cast(x, dtype='bfloat16')
sub amp_cast { AI::MXTpu::op('amp_cast', @_) }

# amp_multicast(*arrays, num_outputs=1, cast_narrow=False)
sub amp_multicast { AI::MXTpu::op('amp_multicast', @_) }

# arange(start=0.0, stop=None, step=1.0, repeat=1, infer_range=False, dtype='float32')
sub arange { AI::MXTpu::op('arange', @_) }

# arccos(...)
sub arccos { AI::MXTpu::op('arccos', @_) }

# arccosh(...)
sub arccosh { AI::MXTpu::op('arccosh', @_) }

# arcsin(...)
sub arcsin { AI::MXTpu::op('arcsin', @_) }

# arcsinh(...)
sub arcsinh { AI::MXTpu::op('arcsinh', @_) }

# arctan(...)
sub arctan { AI::MXTpu::op('arctan', @_) }

# arctan2(...)
sub arctan2 { AI::MXTpu::op('arctan2', @_) }

# arctanh(...)
sub arctanh { AI::MXTpu::op('arctanh', @_) }

# argmax(x, axis=None, keepdims=False)
sub argmax { AI::MXTpu::op('argmax', @_) }

# argmax_channel(x)
sub argmax_channel { AI::MXTpu::op('argmax_channel', @_) }

# argmin(x, axis=None, keepdims=False)
sub argmin { AI::MXTpu::op('argmin', @_) }

# argsort(x, axis=-1, is_ascend=True, dtype='float32')
sub argsort { AI::MXTpu::op('argsort', @_) }

# batch_dot(a, b, transpose_a=False, transpose_b=False, precision=None)
sub batch_dot { AI::MXTpu::op('batch_dot', @_) }

# batch_norm(x, gamma, beta, moving_mean, moving_var, eps=0.001, momentum=0.9, fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1, cudnn_off=False, min_calib_range=None, max_calib_range=None, _training=True)
sub batch_norm { AI::MXTpu::op('batch_norm', @_) }

# batch_take(a, indices)
sub batch_take { AI::MXTpu::op('batch_take', @_) }

# bernoulli(p, key=None, dtype='float32')
sub bernoulli { AI::MXTpu::op('bernoulli', @_) }

# bilinear_resize_2d(data, height=1, width=1, scale_height=None, scale_width=None, mode='size')
sub bilinear_resize_2d { AI::MXTpu::op('bilinear_resize_2d', @_) }

# bilinear_sampler(data, grid, cudnn_off=False)
sub bilinear_sampler { AI::MXTpu::op('bilinear_sampler', @_) }

# bipartite_matching(data, threshold=1e-12, is_ascend=False, topk=-1)
sub bipartite_matching { AI::MXTpu::op('bipartite_matching', @_) }

# blackman(M=1, dtype='float32', ctx=None)
sub blackman { AI::MXTpu::op('blackman', @_) }

# box_iou(lhs, rhs, format='corner')
sub box_iou { AI::MXTpu::op('box_iou', @_) }

# box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2, score_index=1, id_index=-1, background_id=-1, force_suppress=False, in_format='corner', out_format='corner')
sub box_nms { AI::MXTpu::op('box_nms', @_) }

# box_non_maximum_suppression(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2, score_index=1, id_index=-1, background_id=-1, force_suppress=False, in_format='corner', out_format='corner')
sub box_non_maximum_suppression { AI::MXTpu::op('box_non_maximum_suppression', @_) }

# broadcast_add(...)
sub broadcast_add { AI::MXTpu::op('broadcast_add', @_) }

# broadcast_arctan2(...)
sub broadcast_arctan2 { AI::MXTpu::op('broadcast_arctan2', @_) }

# broadcast_axes(x, axis=(), size=())
sub broadcast_axes { AI::MXTpu::op('broadcast_axes', @_) }

# broadcast_axis(x, axis=(), size=())
sub broadcast_axis { AI::MXTpu::op('broadcast_axis', @_) }

# broadcast_div(...)
sub broadcast_div { AI::MXTpu::op('broadcast_div', @_) }

# broadcast_divide(...)
sub broadcast_divide { AI::MXTpu::op('broadcast_divide', @_) }

# broadcast_equal(a, b)
sub broadcast_equal { AI::MXTpu::op('broadcast_equal', @_) }

# broadcast_greater(a, b)
sub broadcast_greater { AI::MXTpu::op('broadcast_greater', @_) }

# broadcast_greater_equal(a, b)
sub broadcast_greater_equal { AI::MXTpu::op('broadcast_greater_equal', @_) }

# broadcast_hypot(...)
sub broadcast_hypot { AI::MXTpu::op('broadcast_hypot', @_) }

# broadcast_lesser(a, b)
sub broadcast_lesser { AI::MXTpu::op('broadcast_lesser', @_) }

# broadcast_lesser_equal(a, b)
sub broadcast_lesser_equal { AI::MXTpu::op('broadcast_lesser_equal', @_) }

# broadcast_like(x, like, lhs_axes=None, rhs_axes=None)
sub broadcast_like { AI::MXTpu::op('broadcast_like', @_) }

# broadcast_logical_and(a, b)
sub broadcast_logical_and { AI::MXTpu::op('broadcast_logical_and', @_) }

# broadcast_logical_or(a, b)
sub broadcast_logical_or { AI::MXTpu::op('broadcast_logical_or', @_) }

# broadcast_logical_xor(a, b)
sub broadcast_logical_xor { AI::MXTpu::op('broadcast_logical_xor', @_) }

# broadcast_maximum(...)
sub broadcast_maximum { AI::MXTpu::op('broadcast_maximum', @_) }

# broadcast_minimum(...)
sub broadcast_minimum { AI::MXTpu::op('broadcast_minimum', @_) }

# broadcast_mod(...)
sub broadcast_mod { AI::MXTpu::op('broadcast_mod', @_) }

# broadcast_mul(...)
sub broadcast_mul { AI::MXTpu::op('broadcast_mul', @_) }

# broadcast_multiply(...)
sub broadcast_multiply { AI::MXTpu::op('broadcast_multiply', @_) }

# broadcast_not_equal(a, b)
sub broadcast_not_equal { AI::MXTpu::op('broadcast_not_equal', @_) }

# broadcast_pow(...)
sub broadcast_pow { AI::MXTpu::op('broadcast_pow', @_) }

# broadcast_power(...)
sub broadcast_power { AI::MXTpu::op('broadcast_power', @_) }

# broadcast_sub(...)
sub broadcast_sub { AI::MXTpu::op('broadcast_sub', @_) }

# broadcast_subtract(...)
sub broadcast_subtract { AI::MXTpu::op('broadcast_subtract', @_) }

# broadcast_to(x, shape=None)
sub broadcast_to { AI::MXTpu::op('broadcast_to', @_) }

# calibrate_entropy(hist, hist_edges, num_quantized_bins=255)
sub calibrate_entropy { AI::MXTpu::op('calibrate_entropy', @_) }

# cast(x, dtype='float32')
sub cast { AI::MXTpu::op('cast', @_) }

# cast_storage(data, stype='default')
sub cast_storage { AI::MXTpu::op('cast_storage', @_) }

# cbrt(...)
sub cbrt { AI::MXTpu::op('cbrt', @_) }

# ceil(...)
sub ceil { AI::MXTpu::op('ceil', @_) }

# choose_element_0index(lhs, rhs)
sub choose_element_0index { AI::MXTpu::op('choose_element_0index', @_) }

# clip(x, a_min=None, a_max=None)
sub clip { AI::MXTpu::op('clip', @_) }

# concat(*xs, dim=1)
sub concat { AI::MXTpu::op('concat', @_) }

# concatenate(*xs, dim=1)
sub concatenate { AI::MXTpu::op('concatenate', @_) }

# contrib_ctc_loss(pred, label, pred_lengths=None, label_lengths=None, layout='NTC', label_layout='NT')
sub contrib_ctc_loss { AI::MXTpu::op('contrib_ctc_loss', @_) }

# convolution(x, weight, bias=None, kernel=None, stride=None, dilate=None, pad=None, num_filter=None, num_group=1, no_bias=False, layout='NCHW', cudnn_tune=None, cudnn_off=False, workspace=1024, precision=None)
sub convolution { AI::MXTpu::op('convolution', @_) }

# correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1, stride2=1, pad_size=0, is_multiply=True)
sub correlation { AI::MXTpu::op('correlation', @_) }

# cos(...)
sub cos_ { AI::MXTpu::op('cos', @_) }

# cosh(...)
sub cosh { AI::MXTpu::op('cosh', @_) }

# count_sketch(data, h, s, out_dim=1, processing_batch_size=32)
sub count_sketch { AI::MXTpu::op('count_sketch', @_) }

# crop(x, begin=(), end=(), step=())
sub crop { AI::MXTpu::op('crop', @_) }

# crop_like(data, *crop_like, num_args=1, offset=(0, 0), h_w=(0, 0), center_crop=False)
sub crop_like { AI::MXTpu::op('crop_like', @_) }

# ctc_loss(pred, label, pred_lengths=None, label_lengths=None, layout='NTC', label_layout='NT')
sub ctc_loss { AI::MXTpu::op('ctc_loss', @_) }

# cumsum(x, axis=None, dtype=None)
sub cumsum { AI::MXTpu::op('cumsum', @_) }

# deconvolution(x, weight, bias=None, kernel=None, stride=None, dilate=None, pad=None, adj=None, target_shape=None, num_filter=None, num_group=1, no_bias=True, layout='NCHW', cudnn_tune=None, cudnn_off=False, workspace=512, precision=None)
sub deconvolution { AI::MXTpu::op('deconvolution', @_) }

# degrees(...)
sub degrees { AI::MXTpu::op('degrees', @_) }

# depth_to_space(x, block_size=1)
sub depth_to_space { AI::MXTpu::op('depth_to_space', @_) }

# diag(x, k=0, axis1=0, axis2=1)
sub diag { AI::MXTpu::op('diag', @_) }

# divide(...)
sub divide { AI::MXTpu::op('divide', @_) }

# dot(a, b, transpose_a=False, transpose_b=False, precision=None)
sub dot_ { AI::MXTpu::op('dot', @_) }

# dropout(x, key=None, p=0.5, mode='training', axes=(), _training=True, cudnn_off=False)
sub dropout { AI::MXTpu::op('dropout', @_) }

# elemwise_add(...)
sub elemwise_add { AI::MXTpu::op('elemwise_add', @_) }

# elemwise_div(...)
sub elemwise_div { AI::MXTpu::op('elemwise_div', @_) }

# elemwise_divide(...)
sub elemwise_divide { AI::MXTpu::op('elemwise_divide', @_) }

# elemwise_mul(...)
sub elemwise_mul { AI::MXTpu::op('elemwise_mul', @_) }

# elemwise_multiply(...)
sub elemwise_multiply { AI::MXTpu::op('elemwise_multiply', @_) }

# elemwise_sub(...)
sub elemwise_sub { AI::MXTpu::op('elemwise_sub', @_) }

# elemwise_subtract(...)
sub elemwise_subtract { AI::MXTpu::op('elemwise_subtract', @_) }

# elemwise_sum(*xs)
sub elemwise_sum { AI::MXTpu::op('elemwise_sum', @_) }

# embedding(data, weight, input_dim=None, output_dim=None, dtype=None, sparse_grad=False)
sub embedding { AI::MXTpu::op('embedding', @_) }

# equal(a, b)
sub equal { AI::MXTpu::op('equal', @_) }

# erf(...)
sub erf { AI::MXTpu::op('erf', @_) }

# erfinv(...)
sub erfinv { AI::MXTpu::op('erfinv', @_) }

# exp(...)
sub exp_ { AI::MXTpu::op('exp', @_) }

# expand_dims(x, axis=0)
sub expand_dims { AI::MXTpu::op('expand_dims', @_) }

# expm1(...)
sub expm1 { AI::MXTpu::op('expm1', @_) }

# extracttrian(a, offset=0, lower=True)
sub extracttrian { AI::MXTpu::op('extracttrian', @_) }

# eye(N=1, M=0, k=0, dtype='float32')
sub eye { AI::MXTpu::op('eye', @_) }

# fft(data, compute_size=128)
sub fft { AI::MXTpu::op('fft', @_) }

# fill_element_0index(lhs, mhs, rhs)
sub fill_element_0index { AI::MXTpu::op('fill_element_0index', @_) }

# fix(...)
sub fix { AI::MXTpu::op('fix', @_) }

# flatten(x)
sub flatten { AI::MXTpu::op('flatten', @_) }

# flip(x, axis=())
sub flip_ { AI::MXTpu::op('flip', @_) }

# floor(...)
sub floor { AI::MXTpu::op('floor', @_) }

# ftml_update(weight, grad, d, v, z, lr=None, t=1, beta1=0.6, beta2=0.999, epsilon=1e-08, wd=0.0, rescale_grad=1.0, clip_grad=-1.0)
sub ftml_update { AI::MXTpu::op('ftml_update', @_) }

# ftrl_update(weight, grad, z, n, lr=None, lamda1=0.01, beta=1.0, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub ftrl_update { AI::MXTpu::op('ftrl_update', @_) }

# full(shape=(), value=0.0, dtype='float32')
sub full { AI::MXTpu::op('full', @_) }

# fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False, flatten=True, precision=None)
sub fully_connected { AI::MXTpu::op('fully_connected', @_) }

# gamma(...)
sub gamma { AI::MXTpu::op('gamma', @_) }

# gammaln(...)
sub gammaln { AI::MXTpu::op('gammaln', @_) }

# gather_nd(data, indices)
sub gather_nd { AI::MXTpu::op('gather_nd', @_) }

# greater(a, b)
sub greater { AI::MXTpu::op('greater', @_) }

# greater_equal(a, b)
sub greater_equal { AI::MXTpu::op('greater_equal', @_) }

# grid_generator(data, transform_type='affine', target_shape=(0, 0))
sub grid_generator { AI::MXTpu::op('grid_generator', @_) }

# group_adagrad_update(weight, grad, history, lr=None, epsilon=1e-07, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub group_adagrad_update { AI::MXTpu::op('group_adagrad_update', @_) }

# group_norm(x, gamma, beta, num_groups=1, eps=1e-05)
sub group_norm { AI::MXTpu::op('group_norm', @_) }

# hamming(M=1, dtype='float32', ctx=None)
sub hamming { AI::MXTpu::op('hamming', @_) }

# hanning(M=1, dtype='float32', ctx=None)
sub hanning { AI::MXTpu::op('hanning', @_) }

# hard_sigmoid(x, alpha=0.2, beta=0.5)
sub hard_sigmoid { AI::MXTpu::op('hard_sigmoid', @_) }

# hawkesll(mu, alpha, beta, state, lags, marks, valid_length, max_time)
sub hawkesll { AI::MXTpu::op('hawkesll', @_) }

# histogram(data, bin_cnt=10, range=None)
sub histogram { AI::MXTpu::op('histogram', @_) }

# hypot(...)
sub hypot { AI::MXTpu::op('hypot', @_) }

# identity(x)
sub identity { AI::MXTpu::op('identity', @_) }

# identity_attach_kl_sparse_reg(data, sparseness_target=0.1, penalty=0.001, momentum=0.9)
sub identity_attach_kl_sparse_reg { AI::MXTpu::op('identity_attach_kl_sparse_reg', @_) }

# ifft(data, compute_size=128)
sub ifft { AI::MXTpu::op('ifft', @_) }

# image_crop(data, x=0, y=0, width=1, height=1)
sub image_crop { AI::MXTpu::op('image_crop', @_) }

# image_flip_left_right(data)
sub image_flip_left_right { AI::MXTpu::op('image_flip_left_right', @_) }

# image_flip_top_bottom(data)
sub image_flip_top_bottom { AI::MXTpu::op('image_flip_top_bottom', @_) }

# image_normalize(data, mean=0.0, std=1.0)
sub image_normalize { AI::MXTpu::op('image_normalize', @_) }

# image_random_brightness(data, key=None, min_factor=0.0, max_factor=1.0)
sub image_random_brightness { AI::MXTpu::op('image_random_brightness', @_) }

# image_random_color_jitter(data, key=None, brightness=0.0, contrast=0.0, saturation=0.0, hue=0.0)
sub image_random_color_jitter { AI::MXTpu::op('image_random_color_jitter', @_) }

# image_random_contrast(data, key=None, min_factor=0.0, max_factor=1.0)
sub image_random_contrast { AI::MXTpu::op('image_random_contrast', @_) }

# image_random_flip_left_right(data, key=None, p=0.5)
sub image_random_flip_left_right { AI::MXTpu::op('image_random_flip_left_right', @_) }

# image_random_flip_top_bottom(data, key=None, p=0.5)
sub image_random_flip_top_bottom { AI::MXTpu::op('image_random_flip_top_bottom', @_) }

# image_random_hue(data, key=None, min_factor=0.0, max_factor=1.0)
sub image_random_hue { AI::MXTpu::op('image_random_hue', @_) }

# image_random_lighting(data, key=None, alpha_std=0.05)
sub image_random_lighting { AI::MXTpu::op('image_random_lighting', @_) }

# image_random_saturation(data, key=None, min_factor=0.0, max_factor=1.0)
sub image_random_saturation { AI::MXTpu::op('image_random_saturation', @_) }

# image_resize(data, size=(0, 0), keep_ratio=False, interp=1)
sub image_resize { AI::MXTpu::op('image_resize', @_) }

# image_to_tensor(data)
sub image_to_tensor { AI::MXTpu::op('image_to_tensor', @_) }

# index_array(data, axes=None)
sub index_array { AI::MXTpu::op('index_array', @_) }

# index_copy(data, index, new_tensor)
sub index_copy { AI::MXTpu::op('index_copy', @_) }

# instance_norm(x, gamma, beta, eps=0.001)
sub instance_norm { AI::MXTpu::op('instance_norm', @_) }

# khatri_rao(*mats)
sub khatri_rao { AI::MXTpu::op('khatri_rao', @_) }

# l2_normalization(x, eps=1e-10, mode='instance')
sub l2_normalization { AI::MXTpu::op('l2_normalization', @_) }

# lamb_update_phase1(weight, grad, mean, var, lr=None, beta1=0.9, beta2=0.999, epsilon=1e-06, t=1, bias_correction=True, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub lamb_update_phase1 { AI::MXTpu::op('lamb_update_phase1', @_) }

# lamb_update_phase2(weight, g, r1, r2, lr=None, lower_bound=-1.0, upper_bound=-1.0)
sub lamb_update_phase2 { AI::MXTpu::op('lamb_update_phase2', @_) }

# layer_norm(x, gamma, beta, axis=-1, eps=1e-05, output_mean_var=False)
sub layer_norm { AI::MXTpu::op('layer_norm', @_) }

# leaky_relu(x, gamma=None, act_type='leaky', slope=0.25, lower_bound=0.125, upper_bound=0.334)
sub leaky_relu { AI::MXTpu::op('leaky_relu', @_) }

# lesser(a, b)
sub lesser { AI::MXTpu::op('lesser', @_) }

# lesser_equal(a, b)
sub lesser_equal { AI::MXTpu::op('lesser_equal', @_) }

# linalg_det(A)
sub linalg_det { AI::MXTpu::op('linalg_det', @_) }

# linalg_extractdiag(A, offset=0)
sub linalg_extractdiag { AI::MXTpu::op('linalg_extractdiag', @_) }

# linalg_extracttrian(a, offset=0, lower=True)
sub linalg_extracttrian { AI::MXTpu::op('linalg_extracttrian', @_) }

# linalg_gelqf(A)
sub linalg_gelqf { AI::MXTpu::op('linalg_gelqf', @_) }

# linalg_gemm(A, B, C, transpose_a=False, transpose_b=False, alpha=1.0, beta=1.0, axis=-2, precision=None)
sub linalg_gemm { AI::MXTpu::op('linalg_gemm', @_) }

# linalg_gemm2(A, B, transpose_a=False, transpose_b=False, alpha=1.0, axis=-2, precision=None)
sub linalg_gemm2 { AI::MXTpu::op('linalg_gemm2', @_) }

# linalg_inverse(A)
sub linalg_inverse { AI::MXTpu::op('linalg_inverse', @_) }

# linalg_makediag(d, offset=0)
sub linalg_makediag { AI::MXTpu::op('linalg_makediag', @_) }

# linalg_maketrian(a, offset=0, lower=True)
sub linalg_maketrian { AI::MXTpu::op('linalg_maketrian', @_) }

# linalg_potrf(A, lower=True)
sub linalg_potrf { AI::MXTpu::op('linalg_potrf', @_) }

# linalg_potri(A, lower=True)
sub linalg_potri { AI::MXTpu::op('linalg_potri', @_) }

# linalg_slogdet(A)
sub linalg_slogdet { AI::MXTpu::op('linalg_slogdet', @_) }

# linalg_sumlogdiag(A)
sub linalg_sumlogdiag { AI::MXTpu::op('linalg_sumlogdiag', @_) }

# linalg_syevd(a)
sub linalg_syevd { AI::MXTpu::op('linalg_syevd', @_) }

# linalg_syrk(A, transpose=False, alpha=1.0, precision=None)
sub linalg_syrk { AI::MXTpu::op('linalg_syrk', @_) }

# linalg_trmm(A, B, transpose=False, rightside=False, lower=True, alpha=1.0, precision=None)
sub linalg_trmm { AI::MXTpu::op('linalg_trmm', @_) }

# linalg_trsm(A, B, transpose=False, rightside=False, lower=True, alpha=1.0)
sub linalg_trsm { AI::MXTpu::op('linalg_trsm', @_) }

# linear_regression_output(data, label, grad_scale=1.0)
sub linear_regression_output { AI::MXTpu::op('linear_regression_output', @_) }

# linspace(start=0.0, stop=1.0, num=50, endpoint=True, dtype='float32')
sub linspace { AI::MXTpu::op('linspace', @_) }

# log(x)
sub log_ { AI::MXTpu::op('log', @_) }

# log10(...)
sub log10 { AI::MXTpu::op('log10', @_) }

# log1p(...)
sub log1p { AI::MXTpu::op('log1p', @_) }

# log2(...)
sub log2 { AI::MXTpu::op('log2', @_) }

# log_softmax(x, axis=-1, temperature=None, dtype=None)
sub log_softmax { AI::MXTpu::op('log_softmax', @_) }

# logical_and(a, b)
sub logical_and { AI::MXTpu::op('logical_and', @_) }

# logical_not(x)
sub logical_not { AI::MXTpu::op('logical_not', @_) }

# logical_or(a, b)
sub logical_or { AI::MXTpu::op('logical_or', @_) }

# logical_xor(a, b)
sub logical_xor { AI::MXTpu::op('logical_xor', @_) }

# logistic_regression_output(data, label, grad_scale=1.0)
sub logistic_regression_output { AI::MXTpu::op('logistic_regression_output', @_) }

# lrn(x, alpha=0.0001, beta=0.75, knorm=2.0, nsize=5)
sub lrn { AI::MXTpu::op('lrn', @_) }

# mae_regression_output(data, label, grad_scale=1.0)
sub mae_regression_output { AI::MXTpu::op('mae_regression_output', @_) }

# make_loss(x, grad_scale=1.0, valid_thresh=0.0, normalization='null')
sub make_loss { AI::MXTpu::op('make_loss', @_) }

# maketrian(a, offset=0, lower=True)
sub maketrian { AI::MXTpu::op('maketrian', @_) }

# max(x, axis=None, keepdims=False, exclude=False)
sub max_ { AI::MXTpu::op('max', @_) }

# max_axis(x, axis=None, keepdims=False, exclude=False)
sub max_axis { AI::MXTpu::op('max_axis', @_) }

# maximum(...)
sub maximum { AI::MXTpu::op('maximum', @_) }

# mean(x, axis=None, keepdims=False, exclude=False)
sub mean { AI::MXTpu::op('mean', @_) }

# min(x, axis=None, keepdims=False, exclude=False)
sub min_ { AI::MXTpu::op('min', @_) }

# min_axis(x, axis=None, keepdims=False, exclude=False)
sub min_axis { AI::MXTpu::op('min_axis', @_) }

# minimum(...)
sub minimum { AI::MXTpu::op('minimum', @_) }

# mod(...)
sub mod { AI::MXTpu::op('mod', @_) }

# moments(data, axes=None, keepdims=False)
sub moments { AI::MXTpu::op('moments', @_) }

# mp_adamw_update(weight, grad, mean, var, weight32, rescale_grad=1.0, lr=None, eta=None, beta1=0.9, beta2=0.999, epsilon=1e-08, wd=0.0, clip_gradient=-1.0)
sub mp_adamw_update { AI::MXTpu::op('mp_adamw_update', @_) }

# mp_nag_mom_update(weight, grad, mom, weight32, lr=None, momentum=0.0, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub mp_nag_mom_update { AI::MXTpu::op('mp_nag_mom_update', @_) }

# mp_sgd_mom_update(weight, grad, mom, weight32, lr=None, momentum=0.0, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True)
sub mp_sgd_mom_update { AI::MXTpu::op('mp_sgd_mom_update', @_) }

# mp_sgd_update(weight, grad, weight32, lr=None, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True)
sub mp_sgd_update { AI::MXTpu::op('mp_sgd_update', @_) }

# multi_all_finite(*arrays, num_arrays=1, init_output=True)
sub multi_all_finite { AI::MXTpu::op('multi_all_finite', @_) }

# multi_lars(lrs, weights_sum_sq, grads_sum_sq, wds, eta=0.001, eps=1e-08, rescale_grad=1.0)
sub multi_lars { AI::MXTpu::op('multi_lars', @_) }

# multi_mp_sgd_mom_update(*data, lrs=None, wds=None, num_weights=1, momentum=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub multi_mp_sgd_mom_update { AI::MXTpu::op('multi_mp_sgd_mom_update', @_) }

# multi_mp_sgd_update(*data, lrs=None, wds=None, num_weights=1, rescale_grad=1.0, clip_gradient=-1.0)
sub multi_mp_sgd_update { AI::MXTpu::op('multi_mp_sgd_update', @_) }

# multi_sgd_mom_update(*data, lrs=None, wds=None, num_weights=1, momentum=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub multi_sgd_mom_update { AI::MXTpu::op('multi_sgd_mom_update', @_) }

# multi_sgd_update(*data, lrs=None, wds=None, num_weights=1, rescale_grad=1.0, clip_gradient=-1.0)
sub multi_sgd_update { AI::MXTpu::op('multi_sgd_update', @_) }

# multi_sum_sq(*arrays, num_arrays=1)
sub multi_sum_sq { AI::MXTpu::op('multi_sum_sq', @_) }

# multibox_detection(cls_pred, loc_pred, anchors, clip=True, threshold=0.01, background_id=0, nms_threshold=0.5, force_suppress=False, variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1)
sub multibox_detection { AI::MXTpu::op('multibox_detection', @_) }

# multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False, steps=(-1.0, -1.0), offsets=(0.5, 0.5))
sub multibox_prior { AI::MXTpu::op('multibox_prior', @_) }

# multinomial(data, key=None, shape=(), get_prob=False, dtype='int32')
sub multinomial { AI::MXTpu::op('multinomial', @_) }

# multiply(...)
sub multiply { AI::MXTpu::op('multiply', @_) }

# nag_mom_update(weight, grad, mom, lr=None, momentum=0.0, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub nag_mom_update { AI::MXTpu::op('nag_mom_update', @_) }

# nanprod(x, axis=None, keepdims=False, exclude=False)
sub nanprod { AI::MXTpu::op('nanprod', @_) }

# nansum(x, axis=None, keepdims=False, exclude=False)
sub nansum { AI::MXTpu::op('nansum', @_) }

# negative(...)
sub negative { AI::MXTpu::op('negative', @_) }

# norm(x, ord=2, axis=None, keepdims=False)
sub norm { AI::MXTpu::op('norm', @_) }

# norm_fro(A)
sub norm_fro { AI::MXTpu::op('norm_fro', @_) }

# normal(key=None, loc=0.0, scale=1.0, shape=(), dtype='float32', ctx=None)
sub normal { AI::MXTpu::op('normal', @_) }

# not_equal(a, b)
sub not_equal { AI::MXTpu::op('not_equal', @_) }

# one_hot(indices, depth=0, on_value=1.0, off_value=0.0, dtype='float32')
sub one_hot { AI::MXTpu::op('one_hot', @_) }

# ones_like(x)
sub ones_like { AI::MXTpu::op('ones_like', @_) }

# pad(x, mode='constant', pad_width=(), constant_value=0.0)
sub pad { AI::MXTpu::op('pad', @_) }

# pick(x, index, axis=-1, keepdims=False, mode='clip')
sub pick { AI::MXTpu::op('pick', @_) }

# pooling(x, kernel=None, pool_type='max', stride=None, pad=None, global_pool=False, pooling_convention='valid', cudnn_off=False, p_value=2, count_include_pad=True, layout=None)
sub pooling { AI::MXTpu::op('pooling', @_) }

# power(...)
sub power { AI::MXTpu::op('power', @_) }

# preloaded_multi_mp_sgd_mom_update(*data, num_weights=1, momentum=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub preloaded_multi_mp_sgd_mom_update { AI::MXTpu::op('preloaded_multi_mp_sgd_mom_update', @_) }

# preloaded_multi_mp_sgd_update(*data, num_weights=1, rescale_grad=1.0, clip_gradient=-1.0)
sub preloaded_multi_mp_sgd_update { AI::MXTpu::op('preloaded_multi_mp_sgd_update', @_) }

# preloaded_multi_sgd_mom_update(*data, num_weights=1, momentum=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub preloaded_multi_sgd_mom_update { AI::MXTpu::op('preloaded_multi_sgd_mom_update', @_) }

# preloaded_multi_sgd_update(*data, num_weights=1, rescale_grad=1.0, clip_gradient=-1.0)
sub preloaded_multi_sgd_update { AI::MXTpu::op('preloaded_multi_sgd_update', @_) }

# prod(x, axis=None, keepdims=False, exclude=False)
sub prod { AI::MXTpu::op('prod', @_) }

# quadratic(data, a=0.0, b=0.0, c=0.0)
sub quadratic { AI::MXTpu::op('quadratic', @_) }

# quantize_v1(data, min_range, max_range, out_type='int8')
sub quantize_v1 { AI::MXTpu::op('quantize_v1', @_) }

# quantize_v2(data, out_type='int8', min_calib_range=None, max_calib_range=None)
sub quantize_v2 { AI::MXTpu::op('quantize_v2', @_) }

# quantized_act(data, min_data, max_data, act_type='relu')
sub quantized_act { AI::MXTpu::op('quantized_act', @_) }

# quantized_batch_norm(data, gamma, beta, moving_mean, moving_var, min_data, max_data, eps=0.001, min_calib_range=None, max_calib_range=None)
sub quantized_batch_norm { AI::MXTpu::op('quantized_batch_norm', @_) }

# quantized_concat(*args, dim=1, num_args=None)
sub quantized_concat { AI::MXTpu::op('quantized_concat', @_) }

# quantized_conv(data, weight, bias, min_data, max_data, min_weight, max_weight, min_bias, max_bias, kernel=(1, 1), stride=(1, 1), pad=(0, 0), dilate=(1, 1), num_filter=1, num_group=1, no_bias=False, layout='NCHW')
sub quantized_conv { AI::MXTpu::op('quantized_conv', @_) }

# quantized_elemwise_add(lhs, rhs, lhs_min, lhs_max, rhs_min, rhs_max)
sub quantized_elemwise_add { AI::MXTpu::op('quantized_elemwise_add', @_) }

# quantized_flatten(data, min_data, max_data)
sub quantized_flatten { AI::MXTpu::op('quantized_flatten', @_) }

# quantized_fully_connected(data, weight, bias, min_data, max_data, min_weight, max_weight, min_bias, max_bias, num_hidden=1, no_bias=False, flatten=True)
sub quantized_fully_connected { AI::MXTpu::op('quantized_fully_connected', @_) }

# quantized_pooling(data, min_data, max_data, kernel=(2, 2), pool_type='max', stride=(1, 1), pad=(0, 0), global_pool=False)
sub quantized_pooling { AI::MXTpu::op('quantized_pooling', @_) }

# radians(...)
sub radians { AI::MXTpu::op('radians', @_) }

# randint(key=None, low=0, high=1, shape=(), dtype='int32', ctx=None)
sub randint { AI::MXTpu::op('randint', @_) }

# randn(key=None, loc=0.0, scale=1.0, shape=(), dtype='float32', ctx=None)
sub randn { AI::MXTpu::op('randn', @_) }

# random_exponential(key=None, lam=1.0, shape=(), dtype='float32', ctx=None)
sub random_exponential { AI::MXTpu::op('random_exponential', @_) }

# random_gamma(key=None, alpha=1.0, beta=1.0, shape=(), dtype='float32', ctx=None)
sub random_gamma { AI::MXTpu::op('random_gamma', @_) }

# random_generalized_negative_binomial(key=None, mu=1.0, alpha=1.0, shape=(), dtype='float32', ctx=None)
sub random_generalized_negative_binomial { AI::MXTpu::op('random_generalized_negative_binomial', @_) }

# random_negative_binomial(key=None, k=1, p=1.0, shape=(), dtype='float32', ctx=None)
sub random_negative_binomial { AI::MXTpu::op('random_negative_binomial', @_) }

# random_normal(key=None, loc=0.0, scale=1.0, shape=(), dtype='float32', ctx=None)
sub random_normal { AI::MXTpu::op('random_normal', @_) }

# random_poisson(key=None, lam=1.0, shape=(), dtype='float32', ctx=None)
sub random_poisson { AI::MXTpu::op('random_poisson', @_) }

# random_randint(key=None, low=0, high=1, shape=(), dtype='int32', ctx=None)
sub random_randint { AI::MXTpu::op('random_randint', @_) }

# random_uniform(key=None, low=0.0, high=1.0, shape=(), dtype='float32', ctx=None)
sub random_uniform { AI::MXTpu::op('random_uniform', @_) }

# ravel_multi_index(data, shape=())
sub ravel_multi_index { AI::MXTpu::op('ravel_multi_index', @_) }

# rcbrt(x)
sub rcbrt { AI::MXTpu::op('rcbrt', @_) }

# reciprocal(x)
sub reciprocal { AI::MXTpu::op('reciprocal', @_) }

# relu(x)
sub relu { AI::MXTpu::op('relu', @_) }

# repeat(x, repeats=1, axis=None)
sub repeat { AI::MXTpu::op('repeat', @_) }

# requantize(data, min_range, max_range, min_calib_range=None, max_calib_range=None)
sub requantize { AI::MXTpu::op('requantize', @_) }

# reshape(x, shape=None, reverse=False)
sub reshape { AI::MXTpu::op('reshape', @_) }

# reshape_like(lhs, rhs, lhs_begin=None, lhs_end=None, rhs_begin=None, rhs_end=None)
sub reshape_like { AI::MXTpu::op('reshape_like', @_) }

# reverse(x, axis=())
sub reverse_ { AI::MXTpu::op('reverse', @_) }

# rint(...)
sub rint { AI::MXTpu::op('rint', @_) }

# rmsprop_update(weight, grad, n, lr=None, gamma1=0.95, epsilon=1e-08, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, clip_weights=-1.0)
sub rmsprop_update { AI::MXTpu::op('rmsprop_update', @_) }

# rmspropalex_update(weight, grad, n, g, delta, lr=None, gamma1=0.95, gamma2=0.9, epsilon=1e-08, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, clip_weights=-1.0)
sub rmspropalex_update { AI::MXTpu::op('rmspropalex_update', @_) }

# rnn(data, parameters, state, state_cell=None, sequence_length=None, key=None, *, mode='lstm', state_size=None, num_layers=1, bidirectional=False, p=0.0, state_outputs=False, projection_size=None, lstm_state_clip_min=None, lstm_state_clip_max=None, lstm_state_clip_nan=False, use_sequence_length=False, _training=True)
sub rnn { AI::MXTpu::op('rnn', @_) }

# roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0, sample_ratio=-1, position_sensitive=False, aligned=False)
sub roi_align { AI::MXTpu::op('roi_align', @_) }

# roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0)
sub roi_pooling { AI::MXTpu::op('roi_pooling', @_) }

# round(...)
sub round { AI::MXTpu::op('round', @_) }

# rsqrt(x)
sub rsqrt { AI::MXTpu::op('rsqrt', @_) }

# sample_gamma(alpha, beta, key=None, shape=(), dtype='float32')
sub sample_gamma { AI::MXTpu::op('sample_gamma', @_) }

# sample_multinomial(data, key=None, shape=(), get_prob=False, dtype='int32')
sub sample_multinomial { AI::MXTpu::op('sample_multinomial', @_) }

# sample_normal(mu, sigma, key=None, shape=(), dtype='float32')
sub sample_normal { AI::MXTpu::op('sample_normal', @_) }

# sample_uniform(low, high, key=None, shape=(), dtype='float32')
sub sample_uniform { AI::MXTpu::op('sample_uniform', @_) }

# scatter_nd(data, indices, shape=None)
sub scatter_nd { AI::MXTpu::op('scatter_nd', @_) }

# sequence_last(data, sequence_length=None, use_sequence_length=True, axis=0)
sub sequence_last { AI::MXTpu::op('sequence_last', @_) }

# sequence_mask(data, sequence_length=None, use_sequence_length=True, value=0.0, axis=0)
sub sequence_mask { AI::MXTpu::op('sequence_mask', @_) }

# sequence_reverse(data, sequence_length=None, use_sequence_length=True, axis=0)
sub sequence_reverse { AI::MXTpu::op('sequence_reverse', @_) }

# sgd_mom_update(weight, grad, mom, lr=None, momentum=0.0, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True)
sub sgd_mom_update { AI::MXTpu::op('sgd_mom_update', @_) }

# sgd_update(weight, grad, lr=None, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True)
sub sgd_update { AI::MXTpu::op('sgd_update', @_) }

# shape_array(x)
sub shape_array { AI::MXTpu::op('shape_array', @_) }

# shuffle(data, key=None)
sub shuffle { AI::MXTpu::op('shuffle', @_) }

# sigmoid(x)
sub sigmoid { AI::MXTpu::op('sigmoid', @_) }

# sign(...)
sub sign_ { AI::MXTpu::op('sign', @_) }

# signsgd_update(weight, grad, lr=None, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub signsgd_update { AI::MXTpu::op('signsgd_update', @_) }

# signum_update(weight, grad, mom, lr=None, momentum=0.0, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0)
sub signum_update { AI::MXTpu::op('signum_update', @_) }

# sin(...)
sub sin_ { AI::MXTpu::op('sin', @_) }

# sinh(...)
sub sinh { AI::MXTpu::op('sinh', @_) }

# size_array(x)
sub size_array { AI::MXTpu::op('size_array', @_) }

# slice(x, begin=(), end=(), step=())
sub slice { AI::MXTpu::op('slice', @_) }

# slice_axis(x, axis=0, begin=0, end=None)
sub slice_axis { AI::MXTpu::op('slice_axis', @_) }

# slice_like(x, like, axes=())
sub slice_like { AI::MXTpu::op('slice_like', @_) }

# smooth_l1(x, scalar=1.0)
sub smooth_l1 { AI::MXTpu::op('smooth_l1', @_) }

# softmax(x, axis=-1, temperature=None, length=None, use_length=False, dtype=None)
sub softmax { AI::MXTpu::op('softmax', @_) }

# softmax_cross_entropy(data, label)
sub softmax_cross_entropy { AI::MXTpu::op('softmax_cross_entropy', @_) }

# softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0, multi_output=False, use_ignore=False, preserve_shape=False, normalization='null', out_grad=False, smooth_alpha=0.0)
sub softmax_output { AI::MXTpu::op('softmax_output', @_) }

# softmin(x, axis=-1)
sub softmin { AI::MXTpu::op('softmin', @_) }

# softrelu(x)
sub softrelu { AI::MXTpu::op('softrelu', @_) }

# softsign(x)
sub softsign { AI::MXTpu::op('softsign', @_) }

# sort(x, axis=-1, is_ascend=True)
sub sort_ { AI::MXTpu::op('sort', @_) }

# space_to_depth(x, block_size=1)
sub space_to_depth { AI::MXTpu::op('space_to_depth', @_) }

# sparse_adagrad_update(weight, grad, history, lr=None, epsilon=1e-07, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0)
sub sparse_adagrad_update { AI::MXTpu::op('sparse_adagrad_update', @_) }

# sparse_retain(data, indices)
sub sparse_retain { AI::MXTpu::op('sparse_retain', @_) }

# spatial_transformer(data, loc, target_shape=(0, 0), transform_type='affine', sampler_type='bilinear', cudnn_off=None)
sub spatial_transformer { AI::MXTpu::op('spatial_transformer', @_) }

# split(x, num_outputs=1, axis=1, squeeze_axis=False)
sub split_ { AI::MXTpu::op('split', @_) }

# split_v2(data, indices=(), axis=0, squeeze_axis=False, sections=0)
sub split_v2 { AI::MXTpu::op('split_v2', @_) }

# sqrt(...)
sub sqrt_ { AI::MXTpu::op('sqrt', @_) }

# square(...)
sub square { AI::MXTpu::op('square', @_) }

# squeeze(x, axis=None)
sub squeeze { AI::MXTpu::op('squeeze', @_) }

# stack(*xs, axis=0)
sub stack { AI::MXTpu::op('stack', @_) }

# stop_gradient(x)
sub stop_gradient { AI::MXTpu::op('stop_gradient', @_) }

# subtract(...)
sub subtract { AI::MXTpu::op('subtract', @_) }

# sum(x, axis=None, keepdims=False, exclude=False)
sub sum_ { AI::MXTpu::op('sum', @_) }

# sum_axis(x, axis=None, keepdims=False, exclude=False)
sub sum_axis { AI::MXTpu::op('sum_axis', @_) }

# svm_output(data, label, margin=1.0, regularization_coefficient=1.0, use_linear=False)
sub svm_output { AI::MXTpu::op('svm_output', @_) }

# swapaxes(x, dim1=0, dim2=0)
sub swapaxes { AI::MXTpu::op('swapaxes', @_) }

# syevd(a)
sub syevd { AI::MXTpu::op('syevd', @_) }

# take(a, indices, axis=0, mode='clip')
sub take { AI::MXTpu::op('take', @_) }

# tan(...)
sub tan { AI::MXTpu::op('tan', @_) }

# tanh(x)
sub tanh { AI::MXTpu::op('tanh', @_) }

# tile(x, reps=())
sub tile { AI::MXTpu::op('tile', @_) }

# topk(x, axis=-1, k=1, ret_typ='indices', is_ascend=False, dtype='float32')
sub topk { AI::MXTpu::op('topk', @_) }

# transpose(x, axes=None)
sub transpose { AI::MXTpu::op('transpose', @_) }

# trunc(...)
sub trunc { AI::MXTpu::op('trunc', @_) }

# uniform(key=None, low=0.0, high=1.0, shape=(), dtype='float32', ctx=None)
sub uniform { AI::MXTpu::op('uniform', @_) }

# unravel_index(data, shape=())
sub unravel_index { AI::MXTpu::op('unravel_index', @_) }

# upsampling(*data, scale=1, sample_type='nearest', num_args=1, num_filter=0, multi_input_mode='concat', workspace=512)
sub upsampling { AI::MXTpu::op('upsampling', @_) }

# where(cond, x, y)
sub where { AI::MXTpu::op('where', @_) }

# zeros_like(x)
sub zeros_like { AI::MXTpu::op('zeros_like', @_) }

1;
