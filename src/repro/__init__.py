"""repro: balanced-GEMM training/serving framework (Striking the Balance on TPU)."""

__version__ = "1.0.0"
