from repro.data.corpus import CORPUS_SCHEMA, synth_corpus, write_corpus

__all__ = ["CORPUS_SCHEMA", "synth_corpus", "write_corpus"]
