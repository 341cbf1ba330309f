from repro.data.corpus import CORPUS_SCHEMA, synth_corpus, write_corpus
from repro.data.pipeline import PipelineConfig, Prefetcher, TokenPipeline

__all__ = ["CORPUS_SCHEMA", "synth_corpus", "write_corpus",
           "PipelineConfig", "Prefetcher", "TokenPipeline"]
