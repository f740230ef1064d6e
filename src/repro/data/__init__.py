from .tokens import (TokenPipeline, lm_batch_specs, make_lm_batch,
                     synthetic_frames)
from .graph_pipeline import (BatchChain, GraphBatchPipeline, Prefetcher,
                             StagedPrefetcher, assemble_batch,
                             gather_features, sample_batch)

__all__ = ["TokenPipeline", "lm_batch_specs", "make_lm_batch",
           "synthetic_frames", "GraphBatchPipeline", "Prefetcher",
           "StagedPrefetcher", "BatchChain", "assemble_batch",
           "gather_features", "sample_batch"]
