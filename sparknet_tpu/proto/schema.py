"""Schema for the Caffe protobuf dialect spoken by the framework.

This is a hand-written description of the message/field layout of Caffe's
``caffe.proto`` (reference: /root/reference/caffe/src/caffe/proto/caffe.proto) —
the wire-compatible *interface*, re-expressed as Python data so that stock
``.prototxt`` model/solver files and ``.caffemodel`` weight files load unchanged
without depending on protoc or vendoring the original schema file.

A field is declared as ``name: (number, type, label, default)`` where
  * ``number``  protobuf field number (used by the binary wire codec),
  * ``type``    one of the SCALAR_TYPES, an enum name, or a message name,
  * ``label``   'opt' or 'rep' ('rep_packed' for packed repeated scalars),
  * ``default`` proto2 explicit default (None -> type zero-value / unset).
"""

SCALAR_TYPES = {
    "float", "double", "int32", "int64", "uint32", "uint64", "bool",
    "string", "bytes",
}

ENUMS = {
    "Phase": {"TRAIN": 0, "TEST": 1},
    "FillerParameter.VarianceNorm": {"FAN_IN": 0, "FAN_OUT": 1, "AVERAGE": 2},
    "SolverParameter.SnapshotFormat": {"HDF5": 0, "BINARYPROTO": 1},
    "SolverParameter.SolverMode": {"CPU": 0, "GPU": 1},
    "SolverParameter.SolverType": {
        "SGD": 0, "NESTEROV": 1, "ADAGRAD": 2, "RMSPROP": 3, "ADADELTA": 4,
        "ADAM": 5,
    },
    "ParamSpec.DimCheckMode": {"STRICT": 0, "PERMISSIVE": 1},
    "LossParameter.NormalizationMode": {
        "FULL": 0, "VALID": 1, "BATCH_SIZE": 2, "NONE": 3,
    },
    "ConvolutionParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "PoolingParameter.PoolMethod": {"MAX": 0, "AVE": 1, "STOCHASTIC": 2},
    "V0LayerParameter.PoolMethod": {"MAX": 0, "AVE": 1, "STOCHASTIC": 2},
    "PoolingParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "LRNParameter.NormRegion": {"ACROSS_CHANNELS": 0, "WITHIN_CHANNEL": 1},
    "LRNParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "EltwiseParameter.EltwiseOp": {"PROD": 0, "SUM": 1, "MAX": 2},
    "HingeLossParameter.Norm": {"L1": 1, "L2": 2},
    "DataParameter.DB": {"LEVELDB": 0, "LMDB": 1},
    "ReductionParameter.ReductionOp": {"SUM": 1, "ASUM": 2, "SUMSQ": 3, "MEAN": 4},
    "ReLUParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "SigmoidParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "SoftmaxParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "TanHParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "SPPParameter.PoolMethod": {"MAX": 0, "AVE": 1, "STOCHASTIC": 2},
    "SPPParameter.Engine": {"DEFAULT": 0, "CAFFE": 1, "CUDNN": 2},
    "V1LayerParameter.LayerType": {
        "NONE": 0, "ABSVAL": 35, "ACCURACY": 1, "ARGMAX": 30, "BNLL": 2,
        "CONCAT": 3, "CONTRASTIVE_LOSS": 37, "CONVOLUTION": 4, "DATA": 5,
        "DECONVOLUTION": 39, "DROPOUT": 6, "DUMMY_DATA": 32,
        "EUCLIDEAN_LOSS": 7, "ELTWISE": 25, "EXP": 38, "FLATTEN": 8,
        "HDF5_DATA": 9, "HDF5_OUTPUT": 10, "HINGE_LOSS": 28, "IM2COL": 11,
        "IMAGE_DATA": 12, "INFOGAIN_LOSS": 13, "INNER_PRODUCT": 14, "LRN": 15,
        "MEMORY_DATA": 29, "MULTINOMIAL_LOGISTIC_LOSS": 16, "MVN": 34,
        "POOLING": 17, "POWER": 26, "RELU": 18, "SIGMOID": 19,
        "SIGMOID_CROSS_ENTROPY_LOSS": 27, "SILENCE": 36, "SOFTMAX": 20,
        "SOFTMAX_LOSS": 21, "SPLIT": 22, "SLICE": 33, "TANH": 23,
        "WINDOW_DATA": 24, "THRESHOLD": 31,
    },
}

MESSAGES = {
    "BlobShape": {
        "dim": (1, "int64", "rep_packed", None),
    },
    "BlobProto": {
        "shape": (7, "BlobShape", "opt", None),
        "data": (5, "float", "rep_packed", None),
        "diff": (6, "float", "rep_packed", None),
        "double_data": (8, "double", "rep_packed", None),
        "double_diff": (9, "double", "rep_packed", None),
        "num": (1, "int32", "opt", 0),
        "channels": (2, "int32", "opt", 0),
        "height": (3, "int32", "opt", 0),
        "width": (4, "int32", "opt", 0),
    },
    "BlobProtoVector": {
        "blobs": (1, "BlobProto", "rep", None),
    },
    "Datum": {
        "channels": (1, "int32", "opt", None),
        "height": (2, "int32", "opt", None),
        "width": (3, "int32", "opt", None),
        "data": (4, "bytes", "opt", None),
        "label": (5, "int32", "opt", None),
        "float_data": (6, "float", "rep", None),
        "encoded": (7, "bool", "opt", False),
    },
    "FillerParameter": {
        "type": (1, "string", "opt", "constant"),
        "value": (2, "float", "opt", 0.0),
        "min": (3, "float", "opt", 0.0),
        "max": (4, "float", "opt", 1.0),
        "mean": (5, "float", "opt", 0.0),
        "std": (6, "float", "opt", 1.0),
        "sparse": (7, "int32", "opt", -1),
        "variance_norm": (8, "FillerParameter.VarianceNorm", "opt", 0),
    },
    "NetParameter": {
        "name": (1, "string", "opt", None),
        "input": (3, "string", "rep", None),
        "input_shape": (8, "BlobShape", "rep", None),
        "input_dim": (4, "int32", "rep", None),
        "force_backward": (5, "bool", "opt", False),
        "state": (6, "NetState", "opt", None),
        "debug_info": (7, "bool", "opt", False),
        "layer": (100, "LayerParameter", "rep", None),
        "layers": (2, "V1LayerParameter", "rep", None),
    },
    "SolverParameter": {
        "net": (24, "string", "opt", None),
        "net_param": (25, "NetParameter", "opt", None),
        "train_net": (1, "string", "opt", None),
        "test_net": (2, "string", "rep", None),
        "train_net_param": (21, "NetParameter", "opt", None),
        "test_net_param": (22, "NetParameter", "rep", None),
        "train_state": (26, "NetState", "opt", None),
        "test_state": (27, "NetState", "rep", None),
        "test_iter": (3, "int32", "rep", None),
        "test_interval": (4, "int32", "opt", 0),
        "test_compute_loss": (19, "bool", "opt", False),
        "test_initialization": (32, "bool", "opt", True),
        "base_lr": (5, "float", "opt", None),
        "display": (6, "int32", "opt", None),
        "average_loss": (33, "int32", "opt", 1),
        "max_iter": (7, "int32", "opt", None),
        "iter_size": (36, "int32", "opt", 1),
        "lr_policy": (8, "string", "opt", None),
        "gamma": (9, "float", "opt", None),
        "power": (10, "float", "opt", None),
        "momentum": (11, "float", "opt", None),
        "weight_decay": (12, "float", "opt", None),
        "regularization_type": (29, "string", "opt", "L2"),
        "stepsize": (13, "int32", "opt", None),
        "stepvalue": (34, "int32", "rep", None),
        "clip_gradients": (35, "float", "opt", -1.0),
        "snapshot": (14, "int32", "opt", 0),
        "snapshot_prefix": (15, "string", "opt", None),
        "snapshot_diff": (16, "bool", "opt", False),
        "snapshot_format": (37, "SolverParameter.SnapshotFormat", "opt", 1),
        "solver_mode": (17, "SolverParameter.SolverMode", "opt", 1),
        "device_id": (18, "int32", "opt", 0),
        "random_seed": (20, "int64", "opt", -1),
        "type": (40, "string", "opt", "SGD"),
        "delta": (31, "float", "opt", 1e-8),
        "momentum2": (39, "float", "opt", 0.999),
        "rms_decay": (38, "float", "opt", None),
        "debug_info": (23, "bool", "opt", False),
        "snapshot_after_train": (28, "bool", "opt", True),
        "solver_type": (30, "SolverParameter.SolverType", "opt", 0),
    },
    "SolverState": {
        "iter": (1, "int32", "opt", None),
        "learned_net": (2, "string", "opt", None),
        "history": (3, "BlobProto", "rep", None),
        "current_step": (4, "int32", "opt", 0),
    },
    "NetState": {
        "phase": (1, "Phase", "opt", 1),
        "level": (2, "int32", "opt", 0),
        "stage": (3, "string", "rep", None),
    },
    "NetStateRule": {
        "phase": (1, "Phase", "opt", None),
        "min_level": (2, "int32", "opt", None),
        "max_level": (3, "int32", "opt", None),
        "stage": (4, "string", "rep", None),
        "not_stage": (5, "string", "rep", None),
    },
    "ParamSpec": {
        "name": (1, "string", "opt", None),
        "share_mode": (2, "ParamSpec.DimCheckMode", "opt", None),
        "lr_mult": (3, "float", "opt", 1.0),
        "decay_mult": (4, "float", "opt", 1.0),
    },
    "LayerParameter": {
        "name": (1, "string", "opt", None),
        "type": (2, "string", "opt", None),
        "bottom": (3, "string", "rep", None),
        "top": (4, "string", "rep", None),
        "phase": (10, "Phase", "opt", None),
        "loss_weight": (5, "float", "rep", None),
        "param": (6, "ParamSpec", "rep", None),
        "blobs": (7, "BlobProto", "rep", None),
        "propagate_down": (11, "bool", "rep", None),
        "include": (8, "NetStateRule", "rep", None),
        "exclude": (9, "NetStateRule", "rep", None),
        "transform_param": (100, "TransformationParameter", "opt", None),
        "loss_param": (101, "LossParameter", "opt", None),
        "accuracy_param": (102, "AccuracyParameter", "opt", None),
        "argmax_param": (103, "ArgMaxParameter", "opt", None),
        "batch_norm_param": (139, "BatchNormParameter", "opt", None),
        "concat_param": (104, "ConcatParameter", "opt", None),
        "contrastive_loss_param": (105, "ContrastiveLossParameter", "opt", None),
        "convolution_param": (106, "ConvolutionParameter", "opt", None),
        "data_param": (107, "DataParameter", "opt", None),
        "dropout_param": (108, "DropoutParameter", "opt", None),
        "dummy_data_param": (109, "DummyDataParameter", "opt", None),
        "eltwise_param": (110, "EltwiseParameter", "opt", None),
        "embed_param": (137, "EmbedParameter", "opt", None),
        "exp_param": (111, "ExpParameter", "opt", None),
        "flatten_param": (135, "FlattenParameter", "opt", None),
        "hdf5_data_param": (112, "HDF5DataParameter", "opt", None),
        "hdf5_output_param": (113, "HDF5OutputParameter", "opt", None),
        "hinge_loss_param": (114, "HingeLossParameter", "opt", None),
        "image_data_param": (115, "ImageDataParameter", "opt", None),
        "infogain_loss_param": (116, "InfogainLossParameter", "opt", None),
        "inner_product_param": (117, "InnerProductParameter", "opt", None),
        "log_param": (134, "LogParameter", "opt", None),
        "lrn_param": (118, "LRNParameter", "opt", None),
        "memory_data_param": (119, "MemoryDataParameter", "opt", None),
        "mvn_param": (120, "MVNParameter", "opt", None),
        "pooling_param": (121, "PoolingParameter", "opt", None),
        "power_param": (122, "PowerParameter", "opt", None),
        "prelu_param": (131, "PReLUParameter", "opt", None),
        "python_param": (130, "PythonParameter", "opt", None),
        "reduction_param": (136, "ReductionParameter", "opt", None),
        "relu_param": (123, "ReLUParameter", "opt", None),
        "reshape_param": (133, "ReshapeParameter", "opt", None),
        "sigmoid_param": (124, "SigmoidParameter", "opt", None),
        "softmax_param": (125, "SoftmaxParameter", "opt", None),
        "spp_param": (132, "SPPParameter", "opt", None),
        "slice_param": (126, "SliceParameter", "opt", None),
        "tanh_param": (127, "TanHParameter", "opt", None),
        "threshold_param": (128, "ThresholdParameter", "opt", None),
        "tile_param": (138, "TileParameter", "opt", None),
        "java_data_param": (149, "JavaDataParameter", "opt", None),
        "window_data_param": (129, "WindowDataParameter", "opt", None),
        # sparknet_tpu extensions (numbers chosen above caffe's range)
        "attention_param": (200, "AttentionParameter", "opt", None),
        "layer_norm_param": (201, "LayerNormParameter", "opt", None),
        "moe_param": (202, "MoEParameter", "opt", None),
        "rms_norm_param": (203, "RMSNormParameter", "opt", None),
        "gated_delta_net_param": (204, "GatedDeltaNetParameter", "opt",
                                  None),
        "short_conv_param": (205, "ShortConvParameter", "opt", None),
        "mamba2_param": (206, "Mamba2Parameter", "opt", None),
        "shift_param": (207, "ShiftParameter", "opt", None),
    },
    "TransformationParameter": {
        "scale": (1, "float", "opt", 1.0),
        "mirror": (2, "bool", "opt", False),
        "crop_size": (3, "uint32", "opt", 0),
        "mean_file": (4, "string", "opt", None),
        "mean_value": (5, "float", "rep", None),
        "force_color": (6, "bool", "opt", False),
        "force_gray": (7, "bool", "opt", False),
    },
    "LossParameter": {
        "ignore_label": (1, "int32", "opt", None),
        "normalize": (2, "bool", "opt", True),
    },
    "AccuracyParameter": {
        "top_k": (1, "uint32", "opt", 1),
        "axis": (2, "int32", "opt", 1),
        "ignore_label": (3, "int32", "opt", None),
    },
    "ArgMaxParameter": {
        "out_max_val": (1, "bool", "opt", False),
        "top_k": (2, "uint32", "opt", 1),
        "axis": (3, "int32", "opt", None),
    },
    "BatchNormParameter": {
        "use_global_stats": (1, "bool", "opt", None),
        "moving_average_fraction": (2, "float", "opt", 0.999),
        "eps": (3, "float", "opt", 1e-5),
    },
    "ConcatParameter": {
        "axis": (2, "int32", "opt", 1),
        "concat_dim": (1, "uint32", "opt", 1),
    },
    "ContrastiveLossParameter": {
        "margin": (1, "float", "opt", 1.0),
        "legacy_version": (2, "bool", "opt", False),
    },
    "ConvolutionParameter": {
        "num_output": (1, "uint32", "opt", None),
        "bias_term": (2, "bool", "opt", True),
        "pad": (3, "uint32", "rep", None),
        "kernel_size": (4, "uint32", "rep", None),
        "stride": (6, "uint32", "rep", None),
        "pad_h": (9, "uint32", "opt", 0),
        "pad_w": (10, "uint32", "opt", 0),
        "kernel_h": (11, "uint32", "opt", None),
        "kernel_w": (12, "uint32", "opt", None),
        "stride_h": (13, "uint32", "opt", None),
        "stride_w": (14, "uint32", "opt", None),
        "group": (5, "uint32", "opt", 1),
        "weight_filler": (7, "FillerParameter", "opt", None),
        "bias_filler": (8, "FillerParameter", "opt", None),
        "engine": (15, "ConvolutionParameter.Engine", "opt", 0),
        "axis": (16, "int32", "opt", 1),
        "force_nd_im2col": (17, "bool", "opt", False),
    },
    "DataParameter": {
        "source": (1, "string", "opt", None),
        "batch_size": (4, "uint32", "opt", None),
        "rand_skip": (7, "uint32", "opt", 0),
        "backend": (8, "DataParameter.DB", "opt", 0),
        "scale": (2, "float", "opt", 1.0),
        "mean_file": (3, "string", "opt", None),
        "crop_size": (5, "uint32", "opt", 0),
        "mirror": (6, "bool", "opt", False),
        "force_encoded_color": (9, "bool", "opt", False),
        "prefetch": (10, "uint32", "opt", 4),
    },
    "DropoutParameter": {
        "dropout_ratio": (1, "float", "opt", 0.5),
    },
    "DummyDataParameter": {
        "data_filler": (1, "FillerParameter", "rep", None),
        "shape": (6, "BlobShape", "rep", None),
        "num": (2, "uint32", "rep", None),
        "channels": (3, "uint32", "rep", None),
        "height": (4, "uint32", "rep", None),
        "width": (5, "uint32", "rep", None),
    },
    "EltwiseParameter": {
        "operation": (1, "EltwiseParameter.EltwiseOp", "opt", 1),
        "coeff": (2, "float", "rep", None),
        "stable_prod_grad": (3, "bool", "opt", True),
    },
    "EmbedParameter": {
        "num_output": (1, "uint32", "opt", None),
        "input_dim": (2, "uint32", "opt", None),
        "bias_term": (3, "bool", "opt", True),
        "weight_filler": (4, "FillerParameter", "opt", None),
        "bias_filler": (5, "FillerParameter", "opt", None),
    },
    "ExpParameter": {
        "base": (1, "float", "opt", -1.0),
        "scale": (2, "float", "opt", 1.0),
        "shift": (3, "float", "opt", 0.0),
    },
    "FlattenParameter": {
        "axis": (1, "int32", "opt", 1),
        "end_axis": (2, "int32", "opt", -1),
    },
    "HDF5DataParameter": {
        "source": (1, "string", "opt", None),
        "batch_size": (2, "uint32", "opt", None),
        "shuffle": (3, "bool", "opt", False),
    },
    "HDF5OutputParameter": {
        "file_name": (1, "string", "opt", None),
    },
    "HingeLossParameter": {
        "norm": (1, "HingeLossParameter.Norm", "opt", 1),
    },
    "ImageDataParameter": {
        "source": (1, "string", "opt", None),
        "batch_size": (4, "uint32", "opt", 1),
        "rand_skip": (7, "uint32", "opt", 0),
        "shuffle": (8, "bool", "opt", False),
        "new_height": (9, "uint32", "opt", 0),
        "new_width": (10, "uint32", "opt", 0),
        "is_color": (11, "bool", "opt", True),
        "scale": (2, "float", "opt", 1.0),
        "mean_file": (3, "string", "opt", None),
        "crop_size": (5, "uint32", "opt", 0),
        "mirror": (6, "bool", "opt", False),
        "root_folder": (12, "string", "opt", ""),
    },
    "InfogainLossParameter": {
        "source": (1, "string", "opt", None),
    },
    "InnerProductParameter": {
        "num_output": (1, "uint32", "opt", None),
        "bias_term": (2, "bool", "opt", True),
        "weight_filler": (3, "FillerParameter", "opt", None),
        "bias_filler": (4, "FillerParameter", "opt", None),
        "axis": (5, "int32", "opt", 1),
    },
    "LogParameter": {
        "base": (1, "float", "opt", -1.0),
        "scale": (2, "float", "opt", 1.0),
        "shift": (3, "float", "opt", 0.0),
    },
    "LRNParameter": {
        "local_size": (1, "uint32", "opt", 5),
        "alpha": (2, "float", "opt", 1.0),
        "beta": (3, "float", "opt", 0.75),
        "norm_region": (4, "LRNParameter.NormRegion", "opt", 0),
        "k": (5, "float", "opt", 1.0),
        "engine": (6, "LRNParameter.Engine", "opt", 0),
    },
    "MemoryDataParameter": {
        "batch_size": (1, "uint32", "opt", None),
        "channels": (2, "uint32", "opt", None),
        "height": (3, "uint32", "opt", None),
        "width": (4, "uint32", "opt", None),
    },
    "MVNParameter": {
        "normalize_variance": (1, "bool", "opt", True),
        "across_channels": (2, "bool", "opt", False),
        "eps": (3, "float", "opt", 1e-9),
    },
    "PoolingParameter": {
        "pool": (1, "PoolingParameter.PoolMethod", "opt", 0),
        "pad": (4, "uint32", "opt", 0),
        "pad_h": (9, "uint32", "opt", 0),
        "pad_w": (10, "uint32", "opt", 0),
        "kernel_size": (2, "uint32", "opt", None),
        "kernel_h": (5, "uint32", "opt", None),
        "kernel_w": (6, "uint32", "opt", None),
        "stride": (3, "uint32", "opt", 1),
        "stride_h": (7, "uint32", "opt", None),
        "stride_w": (8, "uint32", "opt", None),
        "engine": (11, "PoolingParameter.Engine", "opt", 0),
        "global_pooling": (12, "bool", "opt", False),
    },
    "PowerParameter": {
        "power": (1, "float", "opt", 1.0),
        "scale": (2, "float", "opt", 1.0),
        "shift": (3, "float", "opt", 0.0),
    },
    "PReLUParameter": {
        "filler": (1, "FillerParameter", "opt", None),
        "channel_shared": (2, "bool", "opt", False),
    },
    "PythonParameter": {
        "module": (1, "string", "opt", None),
        "layer": (2, "string", "opt", None),
        "param_str": (3, "string", "opt", ""),
        "share_in_parallel": (4, "bool", "opt", False),
    },
    "ReductionParameter": {
        "operation": (1, "ReductionParameter.ReductionOp", "opt", 1),
        "axis": (2, "int32", "opt", 0),
        "coeff": (3, "float", "opt", 1.0),
    },
    "ReLUParameter": {
        "negative_slope": (1, "float", "opt", 0.0),
        "engine": (2, "ReLUParameter.Engine", "opt", 0),
    },
    "ReshapeParameter": {
        "shape": (1, "BlobShape", "opt", None),
        "axis": (2, "int32", "opt", 0),
        "num_axes": (3, "int32", "opt", -1),
    },
    "SigmoidParameter": {
        "engine": (1, "SigmoidParameter.Engine", "opt", 0),
    },
    "SliceParameter": {
        "axis": (3, "int32", "opt", 1),
        "slice_point": (2, "uint32", "rep", None),
        "slice_dim": (1, "uint32", "opt", 1),
    },
    "SoftmaxParameter": {
        "engine": (1, "SoftmaxParameter.Engine", "opt", 0),
        "axis": (2, "int32", "opt", 1),
    },
    "SPPParameter": {
        "pyramid_height": (1, "uint32", "opt", None),
        "pool": (2, "SPPParameter.PoolMethod", "opt", 0),
        "engine": (6, "SPPParameter.Engine", "opt", 0),
    },
    "TanHParameter": {
        "engine": (1, "TanHParameter.Engine", "opt", 0),
    },
    "ThresholdParameter": {
        "threshold": (1, "float", "opt", 0.0),
    },
    "TileParameter": {
        "axis": (1, "int32", "opt", 1),
        "tiles": (2, "int32", "opt", None),
    },
    "JavaDataParameter": {
        "shape": (1, "BlobShape", "opt", None),
    },
    "WindowDataParameter": {
        "source": (1, "string", "opt", None),
        "scale": (2, "float", "opt", 1.0),
        "mean_file": (3, "string", "opt", None),
        "batch_size": (4, "uint32", "opt", None),
        "crop_size": (5, "uint32", "opt", 0),
        "mirror": (6, "bool", "opt", False),
        "fg_threshold": (7, "float", "opt", 0.5),
        "bg_threshold": (8, "float", "opt", 0.5),
        "fg_fraction": (9, "float", "opt", 0.25),
        "context_pad": (10, "uint32", "opt", 0),
        "crop_mode": (11, "string", "opt", "warp"),
        "cache_images": (12, "bool", "opt", False),
        "root_folder": (13, "string", "opt", ""),
    },
    # V1 (legacy) layer parameter: enough structure to upgrade old net protos.
    # caffe.proto:1139 — the V0 "layer connection" payload (one per V1
    # `layers` entry in ancient nets); consumed by graph.upgrade.upgrade_v0
    "V0LayerParameter": {
        "name": (1, "string", "opt", None),
        "type": (2, "string", "opt", None),
        "num_output": (3, "uint32", "opt", None),
        "biasterm": (4, "bool", "opt", True),
        "weight_filler": (5, "FillerParameter", "opt", None),
        "bias_filler": (6, "FillerParameter", "opt", None),
        "pad": (7, "uint32", "opt", 0),
        "kernelsize": (8, "uint32", "opt", None),
        "group": (9, "uint32", "opt", 1),
        "stride": (10, "uint32", "opt", 1),
        "pool": (11, "V0LayerParameter.PoolMethod", "opt", 0),
        "dropout_ratio": (12, "float", "opt", 0.5),
        "local_size": (13, "uint32", "opt", 5),
        "alpha": (14, "float", "opt", 1.0),
        "beta": (15, "float", "opt", 0.75),
        "k": (22, "float", "opt", 1.0),
        "source": (16, "string", "opt", None),
        "scale": (17, "float", "opt", 1.0),
        "meanfile": (18, "string", "opt", None),
        "batchsize": (19, "uint32", "opt", None),
        "cropsize": (20, "uint32", "opt", 0),
        "mirror": (21, "bool", "opt", False),
        "blobs": (50, "BlobProto", "rep", None),
        "blobs_lr": (51, "float", "rep", None),
        "weight_decay": (52, "float", "rep", None),
        "rand_skip": (53, "uint32", "opt", 0),
        "det_fg_threshold": (54, "float", "opt", 0.5),
        "det_bg_threshold": (55, "float", "opt", 0.5),
        "det_fg_fraction": (56, "float", "opt", 0.25),
        "det_context_pad": (58, "uint32", "opt", 0),
        "det_crop_mode": (59, "string", "opt", "warp"),
        "new_num": (60, "int32", "opt", 0),
        "new_channels": (61, "int32", "opt", 0),
        "new_height": (62, "int32", "opt", 0),
        "new_width": (63, "int32", "opt", 0),
        "shuffle_images": (64, "bool", "opt", False),
        "concat_dim": (65, "uint32", "opt", 1),
        "hdf5_output_param": (1001, "HDF5OutputParameter", "opt", None),
    },
    "V1LayerParameter": {
        "layer": (1, "V0LayerParameter", "opt", None),
        "bottom": (2, "string", "rep", None),
        "top": (3, "string", "rep", None),
        "name": (4, "string", "opt", None),
        "include": (32, "NetStateRule", "rep", None),
        "exclude": (33, "NetStateRule", "rep", None),
        "type": (5, "V1LayerParameter.LayerType", "opt", None),
        "blobs": (6, "BlobProto", "rep", None),
        "param": (1001, "string", "rep", None),
        "blobs_lr": (7, "float", "rep", None),
        "weight_decay": (8, "float", "rep", None),
        "loss_weight": (35, "float", "rep", None),
        "accuracy_param": (27, "AccuracyParameter", "opt", None),
        "argmax_param": (23, "ArgMaxParameter", "opt", None),
        "concat_param": (9, "ConcatParameter", "opt", None),
        "contrastive_loss_param": (40, "ContrastiveLossParameter", "opt", None),
        "convolution_param": (10, "ConvolutionParameter", "opt", None),
        "data_param": (11, "DataParameter", "opt", None),
        "dropout_param": (12, "DropoutParameter", "opt", None),
        "dummy_data_param": (26, "DummyDataParameter", "opt", None),
        "eltwise_param": (24, "EltwiseParameter", "opt", None),
        "exp_param": (41, "ExpParameter", "opt", None),
        "hdf5_data_param": (13, "HDF5DataParameter", "opt", None),
        "hdf5_output_param": (14, "HDF5OutputParameter", "opt", None),
        "hinge_loss_param": (29, "HingeLossParameter", "opt", None),
        "image_data_param": (15, "ImageDataParameter", "opt", None),
        "infogain_loss_param": (16, "InfogainLossParameter", "opt", None),
        "inner_product_param": (17, "InnerProductParameter", "opt", None),
        "lrn_param": (18, "LRNParameter", "opt", None),
        "memory_data_param": (22, "MemoryDataParameter", "opt", None),
        "mvn_param": (34, "MVNParameter", "opt", None),
        "pooling_param": (19, "PoolingParameter", "opt", None),
        "power_param": (21, "PowerParameter", "opt", None),
        "relu_param": (30, "ReLUParameter", "opt", None),
        "sigmoid_param": (38, "SigmoidParameter", "opt", None),
        "softmax_param": (39, "SoftmaxParameter", "opt", None),
        "slice_param": (31, "SliceParameter", "opt", None),
        "tanh_param": (37, "TanHParameter", "opt", None),
        "threshold_param": (25, "ThresholdParameter", "opt", None),
        "window_data_param": (20, "WindowDataParameter", "opt", None),
        "transform_param": (36, "TransformationParameter", "opt", None),
        "loss_param": (42, "LossParameter", "opt", None),
    },
    # sparknet_tpu extension: attention for the long-context/sequence path.
    "AttentionParameter": {
        "num_heads": (1, "uint32", "opt", 1),
        "head_dim": (2, "uint32", "opt", None),
        "causal": (3, "bool", "opt", False),
        "ring": (4, "bool", "opt", False),
        "weight_filler": (5, "FillerParameter", "opt", None),
        "flash": (6, "bool", "opt", False),   # pallas flash kernel per chip
        # grouped-query form (ops/attention.py): setting num_kv_heads
        # selects separate, bias-free q/k/v/out projections, each
        # key-value head serving num_heads / num_kv_heads query heads
        "num_kv_heads": (7, "uint32", "opt", None),
        "qk_norm": (8, "bool", "opt", False),     # RMSNorm per head on q, k
        "rotary_dim": (9, "uint32", "opt", 0),    # leading dims rotated
        "rope_theta": (10, "float", "opt", 10000.0),
        "output_gate": (11, "bool", "opt", False),  # o * sigmoid(gate)
        "norm_eps": (12, "float", "opt", 1e-6),
        # sliding window (with causal): query i sees keys i - window < j
        # <= i, its own among them; 0 = none. The flash kernel skips the
        # blocks outside the band, the dense path masks the same way.
        "window": (13, "uint32", "opt", 0),
        # with qk_norm: False makes the two norms the plain form
        # y = x / rms(x) * w with w filled with 1 (True, the default:
        # y = x / rms(x) * (1 + w), w filled with 0)
        "qk_norm_zero_centered": (14, "bool", "opt", True),
        # a learned index picks every query's keys (ops/dsa.py; with
        # causal and num_kv_heads): index_heads index queries of
        # index_head_dim and ONE index key a token, rotary on the first
        # half of them, a LayerNorm (the layer's norm_eps) on the key; the
        # index_topk keys with the largest index score are the query's. A
        # second top carries the index's own KL loss; with index_stats a
        # third (weight 0) the share of the selected keys inside a window
        # of index_topk.
        "index_heads": (15, "uint32", "opt", None),
        "index_head_dim": (16, "uint32", "opt", None),
        "index_topk": (17, "uint32", "opt", None),
        "index_stats": (18, "bool", "opt", False),
        # the grouped-query form's out projection filled by this one and
        # not by weight_filler (a model that scales its residual branches'
        # last matrix down at the start)
        "out_filler": (19, "FillerParameter", "opt", None),
        # multi-head latent attention (ops/attention.py; with causal):
        # naming kv_lora_rank selects it, and it needs all five. Queries
        # through a latent of q_lora_rank, keys and values through one of
        # kv_lora_rank, each with an RMSNorm (norm_eps) of its own; a head
        # is [qk_nope_head_dim | qk_rope_head_dim], rotate-half rotary
        # (rope_theta) on the last qk_rope_head_dim alone, the key's rotary
        # part ONE vector a token that all num_heads heads share; a value
        # head has v_head_dim. It has no meaning with window, ring,
        # index_heads, output_gate, qk_norm, num_kv_heads or rotary_dim.
        "q_lora_rank": (20, "uint32", "opt", None),
        "kv_lora_rank": (21, "uint32", "opt", None),
        "qk_nope_head_dim": (22, "uint32", "opt", None),
        "qk_rope_head_dim": (23, "uint32", "opt", None),
        "v_head_dim": (24, "uint32", "opt", None),
        # the grouped-query form's PER-HEAD output gate: one more blob W_g
        # (num_heads, E) after the head norms, o_h * sigmoid(W_g x)_h with
        # one scalar a head and a token (output_gate is the elementwise
        # one, which doubles W_q; a layer takes one of the two)
        "head_gate": (25, "bool", "opt", False),
        # the rotary's frequency table (with rotary_dim): "plain" is
        # rope_theta ** (-2i / rotary_dim); "yarn" blends it with the table
        # divided by rope_factor over the dimensions between the ones that
        # turn rope_beta_fast and rope_beta_slow times inside
        # rope_original_positions (ops/attention.py:rope_table), and
        # rope_scale multiplies cos and sin (absent with yarn: 0.1 ln
        # rope_factor + 1)
        "rope_type": (26, "string", "opt", "plain"),
        "rope_factor": (27, "float", "opt", 1.0),
        "rope_original_positions": (28, "uint32", "opt", None),
        "rope_beta_fast": (29, "float", "opt", 32.0),
        "rope_beta_slow": (30, "float", "opt", 1.0),
        "rope_scale": (31, "double", "opt", None),
    },
    # sparknet_tpu extension: y[i] = x[i + offset] along `axis`; the places
    # that read past either end hold `fill` (a second prediction depth's
    # labels: the next tokens moved one place, the last place a label the
    # loss ignores).
    "ShiftParameter": {
        "axis": (1, "int32", "opt", 1),
        "offset": (2, "int32", "opt", 1),
        "fill": (3, "float", "opt", 0.0),
    },
    # sparknet_tpu extension: last-axis RMS norm, y = x / rms(x) * (1 + w)
    # (zero_centered, w filled with 0) or * w (w filled with 1).
    "RMSNormParameter": {
        "eps": (1, "float", "opt", 1e-6),
        "zero_centered": (2, "bool", "opt", True),
    },
    # sparknet_tpu extension: Gated DeltaNet linear attention
    # (ops/deltanet.py), the recurrent-state mixer.
    "GatedDeltaNetParameter": {
        "num_k_heads": (1, "uint32", "opt", 1),
        "num_v_heads": (2, "uint32", "opt", 1),
        "head_k_dim": (3, "uint32", "opt", 128),
        "head_v_dim": (4, "uint32", "opt", 128),
        "conv_kernel": (5, "uint32", "opt", 4),
        "chunk": (6, "uint32", "opt", 64),
        "norm_eps": (7, "float", "opt", 1e-6),
        "weight_filler": (8, "FillerParameter", "opt", None),
    },
    # sparknet_tpu extension: the gated short convolution (ops/shortconv.py),
    # the mixer of a conv/attention hybrid: in projection to [B | C | u],
    # a causal depthwise conv of `kernel` taps over B * u, gated by C, out
    # projection. conv_filler unset: uniform(+-1/sqrt(kernel)).
    "ShortConvParameter": {
        "kernel": (1, "uint32", "opt", 3),
        "weight_filler": (2, "FillerParameter", "opt", None),
        "conv_filler": (3, "FillerParameter", "opt", None),
    },
    # sparknet_tpu extension: the Mamba-2 state-space mixer (ops/mamba2.py):
    # num_heads heads of head_dim channels, a state of state_size a
    # channel, B and C shared by the heads of one of n_groups groups, a
    # causal depthwise conv of conv_kernel taps with a bias, the scan in
    # chunks of `chunk` tokens, a gated RMSNorm over each group's channels.
    # out_filler fills W_out (unset: weight_filler); the taps and their
    # bias are filled uniform(+-1/sqrt(conv_kernel)); dt_bias is filled so
    # that softplus(dt_bias) lies in [dt_min, dt_max]. With
    # `stats` a second top (weight 0) carries the mean share of a state
    # that survives one chunk.
    "Mamba2Parameter": {
        "num_heads": (1, "uint32", "opt", 1),
        "head_dim": (2, "uint32", "opt", 64),
        "state_size": (3, "uint32", "opt", 128),
        "n_groups": (4, "uint32", "opt", 1),
        "conv_kernel": (5, "uint32", "opt", 4),
        "chunk": (6, "uint32", "opt", 128),
        "norm_eps": (7, "float", "opt", 1e-5),
        "weight_filler": (8, "FillerParameter", "opt", None),
        "out_filler": (9, "FillerParameter", "opt", None),
        "dt_min": (10, "float", "opt", 0.001),
        "dt_max": (11, "float", "opt", 0.1),
        "stats": (12, "bool", "opt", False),
    },
    # sparknet_tpu extension: last-axis layer norm for transformer blocks.
    "LayerNormParameter": {
        "eps": (1, "float", "opt", 1e-5),
        "affine": (2, "bool", "opt", True),   # learned gamma/beta
    },
    # sparknet_tpu extension: switch-style mixture-of-experts FFN with
    # optional expert parallelism (all_to_all over an "expert" mesh axis).
    "MoEParameter": {
        "num_experts": (1, "uint32", "opt", 1),
        "hidden_dim": (2, "uint32", "opt", 0),      # 0 -> 4*embed
        "capacity_factor": (3, "float", "opt", 1.25),
        "expert_parallel": (4, "bool", "opt", False),
        "weight_filler": (5, "FillerParameter", "opt", None),
        # the no-drop form (gated_experts): top_k routing over ALL
        # num_experts with renormalised weights, bias-free SiLU-gated
        # experts, nothing dropped whatever the imbalance. experts_held /
        # first_expert say which of the router's outputs this layer holds
        # weights for (0 held = all): what absent experts would add is
        # left out. shared_hidden_dim > 0 adds a shared expert behind a
        # sigmoid gate. capacity_factor and expert_parallel belong to the
        # top-1 Switch form only.
        "gated_experts": (6, "bool", "opt", False),
        "top_k": (7, "uint32", "opt", 1),
        "norm_topk_prob": (8, "bool", "opt", True),
        "experts_held": (9, "uint32", "opt", 0),
        "first_expert": (10, "uint32", "opt", 0),
        "shared_hidden_dim": (11, "uint32", "opt", 0),
        "tile_rows": (12, "uint32", "opt", 128),
        # the no-drop form's expert, W_down (act(W_gate x) * W_up x):
        # "silu" or "relu". With a second bottom the router reads that one
        # (another normalised view of the residual) and the experts the
        # first.
        "expert_activation": (13, "string", "opt", "silu"),
        # the no-drop form's route. score_function: "softmax" over all the
        # router's outputs, or "sigmoid" of each. selection_bias: one more
        # blob (num_experts,), filled with 0, lr_mult and decay_mult 0 (a
        # buffer no gradient trains): the top_k are the largest of
        # score + bias, their weights the UNBIASED scores. topk_eps is
        # added to the chosen scores' sum before norm_topk_prob divides by
        # it; routed_scaling_factor multiplies the weights.
        "score_function": (14, "string", "opt", "softmax"),
        "selection_bias": (15, "bool", "opt", False),
        "topk_eps": (16, "float", "opt", 0.0),
        "routed_scaling_factor": (17, "float", "opt", 1.0),
        # the no-drop form's EXPERT. expert_gate_matrix false: an expert is
        # TWO matrices, W_down act(W_up x), and the blob list has no
        # w_gate (nor the shared expert a ws_gate); "relu2", relu(u)^2, is
        # an activation of that form alone. shared_gate false: the shared
        # expert is added as it is, with no sigmoid(w_s . x) in front and
        # no blob for it; it takes the experts' form and, in the two-matrix
        # form, their activation. down_filler fills w_down and ws_down
        # (unset: weight_filler).
        "expert_gate_matrix": (18, "bool", "opt", True),
        "shared_gate": (19, "bool", "opt", True),
        "down_filler": (20, "FillerParameter", "opt", None),
    },
}

INT_TYPES = {"int32", "int64", "uint32", "uint64"}


def is_message(type_name):
    return type_name in MESSAGES


def is_enum(type_name):
    return type_name in ENUMS


def zero_value(type_name):
    if type_name in ("float", "double"):
        return 0.0
    if type_name in INT_TYPES:
        return 0
    if type_name == "bool":
        return False
    if type_name == "string":
        return ""
    if type_name == "bytes":
        return b""
    if type_name in ENUMS:
        return 0
    return None
