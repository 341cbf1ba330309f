from repro.ingest.reader import (MeshReader, Prefetcher, ReaderConfig,
                                 ShardedReader, epoch_order, reshard_states)
from repro.ingest.state import STATE_VERSION, ReaderState

__all__ = ["MeshReader", "Prefetcher", "ReaderConfig", "ReaderState", "STATE_VERSION",
           "ShardedReader", "epoch_order", "reshard_states"]
