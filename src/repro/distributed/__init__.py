from .compression import (CompressionSpec, quantize_blockwise,
                          dequantize_blockwise, topk_sparsify,
                          topk_densify, init_error_feedback,
                          compress_with_feedback, hierarchical_psum,
                          hierarchical_psum_sharded)
from .overlap import (ring_all_reduce, ring_all_reduce_sharded,
                      make_accum_train_step)
from .elastic import (plan_mesh, rescale_tree, make_mesh_from_plan,
                      degrade_sequence, ElasticPlan)

__all__ = [
    "CompressionSpec", "quantize_blockwise", "dequantize_blockwise",
    "topk_sparsify", "topk_densify", "init_error_feedback",
    "compress_with_feedback", "hierarchical_psum",
    "hierarchical_psum_sharded",
    "ring_all_reduce", "ring_all_reduce_sharded", "make_accum_train_step",
    "plan_mesh", "rescale_tree", "make_mesh_from_plan", "degrade_sequence",
    "ElasticPlan",
]
