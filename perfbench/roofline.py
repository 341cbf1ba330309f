"""The yardstick of the kernel rooflines: peaks and the algorithm's bytes.

Each function counts the bytes the *algorithm* must move, from the shapes
of one call, never what one route of the implementation happens to do:
a dictionary decode is a gather whether it runs as the one-hot Pallas
grid or as XLA's ``take``, so both count the same.  A roofline share is
the least time the chip could take over the time it took, so it cannot
pass 100% unless the bytes are counted too high or the time leaves part
of the work out.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    bf16_flops: float    # FLOP/s
    hbm_bytes: float     # bytes/s


#: Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def dict_decode_bytes(n: int, d: int) -> int:
    """Gather of ``n`` 32-bit codes through a ``d``-entry dictionary of
    32-bit values: read the codes and the dictionary, write the values.
    No FLOPs: a gather is bound by memory."""
    return 4 * n + 4 * d + 4 * n


def pack_bytes(n: int, selected: int) -> int:
    """Compaction of ``n`` 32-bit values by a one-byte-per-row mask, of
    which ``selected`` rows are kept: read values and mask, write the
    kept values."""
    return 4 * n + n + 4 * selected


def roofline_pct(*, seconds: float, hbm_bytes: float = 0.0,
                 flops: float = 0.0, chip: Peaks) -> float | None:
    """Least time over measured time, in percent; None without a time."""
    if seconds <= 0:
        return None
    least = max(hbm_bytes / chip.hbm_bytes, flops / chip.bf16_flops)
    return 100.0 * least / seconds
