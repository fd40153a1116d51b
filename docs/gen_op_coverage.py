"""Regenerate the family-by-family presence check in op_coverage.md.

Run: JAX_PLATFORMS=cpu python docs/gen_op_coverage.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from mxnet_tpu.ops import registry            # noqa: E402
import mxnet_tpu.numpy as mnp                 # noqa: E402

FAMILIES = {
 "nn core": ["Activation", "BatchNorm", "Convolution", "Deconvolution",
             "Dropout", "Embedding", "FullyConnected", "LRN",
             "LayerNorm", "GroupNorm", "InstanceNorm", "L2Normalization",
             "Pooling", "RNN", "SoftmaxOutput", "softmax", "log_softmax",
             "masked_softmax", "masked_log_softmax", "SequenceLast",
             "SequenceMask", "SequenceReverse", "SliceChannel",
             "UpSampling", "Pad", "Concat", "Flatten", "LeakyReLU",
             "CTCLoss", "SpatialTransformer", "GridGenerator",
             "BilinearSampler", "SwapAxis", "Cast", "BlockGrad",
             "MakeLoss", "Crop", "softmax_activation", "hard_sigmoid",
             "softsign", "relu", "sigmoid", "mish", "log_sigmoid"],
 "contrib detection": [
     "_contrib_DeformableConvolution",
     "_contrib_ModulatedDeformableConvolution",
     "_contrib_DeformablePSROIPooling", "_contrib_PSROIPooling",
     "_contrib_Proposal", "_contrib_MultiProposal", "_contrib_ROIAlign",
     "ROIPooling", "_contrib_RROIAlign", "_contrib_box_iou",
     "_contrib_box_nms", "_contrib_box_encode", "_contrib_box_decode",
     "_contrib_bipartite_matching", "MultiBoxPrior", "MultiBoxTarget",
     "MultiBoxDetection", "_contrib_BilinearResize2D",
     "_contrib_AdaptiveAvgPooling2D", "Correlation",
     "_contrib_SyncBatchNorm"],
 "contrib transformer": [
     "_contrib_interleaved_matmul_selfatt_qk",
     "_contrib_interleaved_matmul_selfatt_valatt",
     "_contrib_interleaved_matmul_encdec_qk",
     "_contrib_interleaved_matmul_encdec_valatt",
     "_contrib_div_sqrt_dim", "_contrib_arange_like"],
 "contrib misc": ["_contrib_quadratic", "_contrib_gradientmultiplier",
                  "_contrib_allclose", "_contrib_getnnz",
                  "_contrib_count_sketch", "_contrib_group_adagrad_update",
                  "_contrib_index_array", "_contrib_index_copy",
                  "_contrib_boolean_mask", "_contrib_fft", "_contrib_ifft"],
 "optimizer": ["sgd_update", "sgd_mom_update", "mp_sgd_update",
               "mp_sgd_mom_update", "nag_mom_update", "mp_nag_mom_update",
               "adam_update", "mp_adam_update", "adamw_update",
               "ftrl_update", "rmsprop_update", "rmspropalex_update",
               "signsgd_update", "signum_update", "lamb_update_phase1",
               "lamb_update_phase2", "mp_lamb_update_phase1",
               "mp_lamb_update_phase2", "multi_sgd_update",
               "multi_sgd_mom_update", "multi_mp_sgd_update",
               "multi_mp_sgd_mom_update", "multi_lars", "multi_sum_sq",
               "multi_all_finite", "preloaded_multi_sgd_update",
               "preloaded_multi_sgd_mom_update", "all_finite",
               "reset_arrays", "_contrib_group_adagrad_update"],
 "random": ["_random_uniform", "_random_normal", "_random_gamma",
            "_random_exponential", "_random_poisson",
            "_random_negative_binomial",
            "_random_generalized_negative_binomial", "_random_randint",
            "_sample_uniform", "_sample_normal", "_sample_gamma",
            "_sample_exponential", "_sample_poisson",
            "_sample_negative_binomial",
            "_sample_generalized_negative_binomial",
            "_sample_multinomial", "_sample_unique_zipfian", "_shuffle"],
 "linalg": ["linalg_gemm", "linalg_gemm2", "linalg_potrf", "linalg_potri",
            "linalg_trmm", "linalg_trsm", "linalg_sumlogdiag",
            "linalg_syrk", "linalg_gelqf", "linalg_syevd", "linalg_det",
            "linalg_slogdet", "linalg_inverse", "linalg_extractdiag",
            "linalg_makediag", "linalg_extracttrian", "khatri_rao"],
 "quantization": ["quantize", "quantize_v2", "dequantize", "requantize",
                  "quantized_conv", "quantized_fully_connected",
                  "quantized_pooling", "quantized_act",
                  "quantized_flatten"],
}


def main():
    have = set(registry.list_ops())
    np_fns = [n for n in dir(mnp)
              if not n.startswith("_") and callable(getattr(mnp, n))]
    print("registry ops:", len(have))
    print("mx.np callables:", len(np_fns))
    bad = []
    for fam, names in FAMILIES.items():
        missing = [n for n in names if n not in have]
        print("%-22s %d/%d present; missing: %s"
              % (fam, len(names) - len(missing), len(names),
                 missing or "none"))
        bad += missing
    if bad:
        raise SystemExit("MISSING: %r" % bad)
    print("all enumerated families fully present")


if __name__ == "__main__":
    main()
