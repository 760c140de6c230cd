"""TPU v5e hardware model (the dry-run target, per spec)."""

PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~the spec's figure)
HBM_BYTES = 16 * 2**30          # 16 GiB per v5e chip

SINGLE_POD_CHIPS = 256          # 16 x 16
MULTI_POD_CHIPS = 512           # 2 pods
