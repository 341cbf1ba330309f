"""Pallas TPU kernels for the scan hot path (DESIGN.md §2).

The paper's scan runs on storage-node Xeons; on a TPU fleet the "free"
compute near the data is the accelerator, so the residual decode work
(dictionary decode, predicate evaluation, selection) gets MXU/VPU kernels:

  predicate_fused   multi-column compare + logic -> byte mask (one pass)
  dict_decode       dictionary gather (one-hot MXU matmul or VPU gather)
  token_pack        masked stream compaction to (fixed buffer, count)
  group_fold        exact per-group sums of measure monomials (MXU)

These are load-bearing for the storage half of the repo: the client-side
decode engine (``repro.aformat.decode.PallasBackend``, reached through
``decode_backend="pallas"`` on any Dataset scan) batches DICT column
chunks through ``decode_dictionary``, lowers flat AND/OR comparison
predicates to ``build_program``/``fused_predicate`` so mask evaluation
fuses across columns, compacts selections with ``pack_tokens``, and
folds an aggregate's kept rows into exact group sums with
``fold_groups``; the
adaptive scheduler prices client placement with the backend's decode
rate.  Every route is bit-exact, so results are byte-identical to the
host path (pinned by ``tests/test_decode.py``).  The kernels compile for
the TPU and run in Pallas's interpreter on the CPU, which serves tests
and rehearsals only; ``repro.kernels.platform`` decides per call and
raises on any other platform.

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd public
wrapper with padding), ref.py (pure-jnp oracle for the allclose tests).
RLE/bit-packed *byte-stream* decode is inherently sequential and stays on
the host path (DESIGN.md §2, non-transferable).
"""

from repro.kernels.dict_decode.ops import decode_dictionary
from repro.kernels.group_fold.ops import fold_groups
from repro.kernels.predicate_fused.ops import build_program, fused_predicate
from repro.kernels.token_pack.ops import pack_tokens

__all__ = ["decode_dictionary", "build_program", "fused_predicate",
           "pack_tokens", "fold_groups"]
