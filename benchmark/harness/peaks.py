"""Published peaks, keyed by `device_kind` as JAX reports it.

Copied from `pilosa_tpu/utils/roofline.PEAK_HBM_GBPS` (PR 21) so that
the yardstick cannot move with the program. A kind that is not in the
table is an error, never a default.
"""

# device_kind -> (HBM GB/s, source)
PEAK_HBM_GBPS = {
    "TPU v5 lite": (819.0, "Google Cloud documentation, 'TPU v5e'"),
    "TPU v5e": (819.0, "Google Cloud documentation, 'TPU v5e'"),
}


def hbm_bytes_per_s(device_kind: str) -> float:
    try:
        return PEAK_HBM_GBPS[device_kind][0] * 1e9
    except KeyError:
        raise KeyError(
            f"no published HBM peak for device kind {device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source"
        ) from None
