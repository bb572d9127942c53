"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports.  A device that is not here is an
error: a share of peak is never computed against a guessed number."""

from __future__ import annotations

from typing import Dict

_V5E = {
    "bf16_flops": 197e12,          # FLOP/s per chip, bfloat16
    "int8_ops": 393e12,            # OP/s per chip, int8
    "hbm_bytes": 16e9,             # bytes of HBM per chip
    "hbm_bytes_per_s": 819e9,      # HBM bandwidth per chip
    "source": "Google Cloud documentation, 'TPU v5e' (per-chip peaks)",
}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict:
    """The peak table of ``device_kind``; raises ``KeyError`` for a kind
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
